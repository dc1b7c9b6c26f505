"""Pytree-parameterized simulator engine (static structure vs workload data).

Public surface:

  * :class:`SimEngine` / :func:`get_engine` — compile-once, run-many
    execution: ``run_grid`` runs a workload x seed cross product (nested
    vmap on one device, lanes over shard_map on more), ``run`` one lane;
  * :class:`WorkloadTables` / :func:`make_workload_tables` — per-workload
    device data as a padded pytree of jit arguments (packed to
    int8/int16 by bucket-derived bounds; see :mod:`.packing`);
  * :func:`build_static_tables` — memoised topology/port/VC constants;
  * :mod:`.arb` — switch-arbitration backends (lax scatter-min
    reference and the bit-exact per-switch Pallas kernel);
  * :class:`SimState`, :class:`SimResult` — simulation state & summary.
"""

from repro.core.engine.arb import arbitrate_lax, make_arbiter
from repro.core.engine.cache import enable_persistent_cache
from repro.core.engine.packing import pack, pack_dtype
from repro.core.engine.route_kernel import make_fused_router
from repro.core.engine.runner import (
    PACKET_FLITS,
    SimEngine,
    SimResult,
    default_lane_backend,
    get_engine,
)
from repro.core.engine.step import SimState, all_done, build_step, init_state
from repro.core.engine.tables import StaticTables, build_static_tables
from repro.core.engine.workload_tables import (
    PreparedWorkload,
    WorkloadTables,
    make_workload_tables,
    shape_bucket,
    stack_tables,
)

__all__ = [
    "PACKET_FLITS",
    "PreparedWorkload",
    "SimEngine",
    "SimResult",
    "SimState",
    "StaticTables",
    "WorkloadTables",
    "all_done",
    "arbitrate_lax",
    "build_static_tables",
    "build_step",
    "default_lane_backend",
    "enable_persistent_cache",
    "get_engine",
    "init_state",
    "make_arbiter",
    "make_fused_router",
    "make_workload_tables",
    "pack",
    "pack_dtype",
    "shape_bucket",
    "stack_tables",
]
