"""Compile rehearsals for a TPU v5e that is described, not attached.

The TPU compiler is installed beside the CPU backend, so the main path's
programs are compiled here for a ``v5e:2x2`` chip at the paper's size
(HyperX n=8, q=2: S=64 switches, 7,680 queue heads), batched over two
lanes as ``SimEngine.run_grid`` batches them.  This catches what the
Pallas interpreter cannot: block layouts Mosaic refuses, ops it cannot
lower, programs that do not fit.  Nothing runs, so nothing here is a
result or a time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""

import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # benchmarks/ is a namespace package at repo root
    sys.path.insert(0, REPO)

from benchmarks.common import PAPER_TOPO, interference_workload  # noqa: E402
from repro.core.engine import (  # noqa: E402
    SimEngine,
    build_static_tables,
    make_arbiter,
    make_fused_router,
    stack_tables,
)

LANES = 2


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read back
    # without a chip: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct((LANES,) + shape, dtype, sharding=sharding)


def _report(name, compiled):
    print(f"{name}: {compiled.memory_analysis()}")


def test_arbiter_compiles_for_v5e(one_chip):
    st = build_static_tables(PAPER_TOPO, mode="omniwar")
    arb = make_arbiter(st.S, st.OUT, st.H, "pallas", interpret=False)
    compiled = jax.jit(jax.vmap(arb)).lower(
        _spec(one_chip, (st.H,)), _spec(one_chip, (st.H,), jnp.uint32),
    ).compile()
    _report("switch_arbitration", compiled)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("mode", ["omniwar", "min"])
def test_fused_router_compiles_for_v5e(one_chip, mode):
    st = build_static_tables(PAPER_TOPO, mode=mode)
    S, H, QN = st.S, st.H, st.q * st.n
    fused = make_fused_router(st, interpret=False)
    compiled = jax.jit(jax.vmap(fused)).lower(
        _spec(one_chip, (H,), bool), _spec(one_chip, (H,), bool),
        _spec(one_chip, (H,)), _spec(one_chip, (H,)), _spec(one_chip, (H,)),
        _spec(one_chip, (H,)), _spec(one_chip, (S, QN), bool),
        _spec(one_chip, ()), _spec(one_chip, (st.NQ,)),
        _spec(one_chip, (S * st.IN,)), _spec(one_chip, (S * st.OUT,)),
        _spec(one_chip, (H, QN)), _spec(one_chip, (H,), jnp.uint32),
    ).compile()
    _report(f"route_arbitrate_fused[{mode}]", compiled)
    assert "tpu_custom_call" in compiled.as_text()


def test_lax_grid_core_compiles_for_v5e(one_chip):
    """The default engine's grid program (workloads x seeds nested vmap),
    for the paper's interference lanes with background."""
    wls = [interference_workload(s, "all_to_all", with_bg=True)
           for s in ("diagonal", "rectangular")]
    engine = SimEngine(PAPER_TOPO, mode="omniwar",
                       num_pools=wls[0].num_pools)
    stacked = stack_tables([engine.prepare(w).tables for w in wls])

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    compiled = engine._runNS.lower(
        jax.tree_util.tree_map(spec, stacked),
        jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
    ).compile()
    _report("grid core (lax)", compiled)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**30  # one v5e chip's HBM
