"""Per-workload device data, as a pytree of jit *arguments*.

The seed simulator baked every workload array into the jit closure, so each
scenario — even with identical shapes — produced a fresh trace.  Here all
per-workload state lives in a :class:`WorkloadTables` NamedTuple (a pytree),
padded to shape *buckets*, and is handed to the compiled step function as a
device argument.  Two consequences:

  * scenarios whose tables land in the same bucket share one compilation
    (the jit cache keys on shapes, not values);
  * same-bucket tables can be ``jnp.stack``-ed along a leading axis and the
    whole while-loop ``jax.vmap``-ed, so an entire strategy x seed sweep is
    one device call.

Padding is semantics-preserving:

  * extra *steps* (T -> T_b) are never walked: the per-rank ``n_steps``
    field keeps the real step count, and the completion / window / injection
    logic compares against it instead of the padded table width;
  * extra *ranks* (R -> R_b) are flagged ``infinite`` (ignored by the
    completion predicate) and mapped to no endpoint (so they never inject);
  * extra *destination slots* (MAXD -> D_b) sit beyond ``deg`` and are never
    dereferenced by the send cursor;
  * extra *fault epochs* (NE -> NE_b) repeat the last real mask at start
    cycle INT32_MAX, so the epoch index never selects them.
"""

from __future__ import annotations

import dataclasses

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine.packing import pack
from repro.route import faults
from repro.route.topology import self_port_mask
from repro.traffic.workload import Workload

I32 = jnp.int32


class WorkloadTables(NamedTuple):
    """All per-workload arrays the step function consumes (R, T, D padded).

    Every leaf is a jnp array so the tuple is a pytree: it can be passed as
    a jit argument, stacked with ``stack_tables`` and vmapped.  The fault
    mask and Valiant intermediate pool have topology-static shapes, so a
    fault-scenario grid batches exactly like a strategy or seed axis.
    """

    rank_ep: jnp.ndarray      # (R,)   endpoint id per rank (pad: 0)
    ep_rank: jnp.ndarray      # (E,)   rank per endpoint, -1 = none
    pool: jnp.ndarray         # (R,)   VC pool per rank
    finite: jnp.ndarray       # (R,)   bool; pad ranks are ~finite
    window: jnp.ndarray       # (R,)   outstanding-step window
    start_t: jnp.ndarray      # (R,)   injection start time (warmup gating)
    n_steps: jnp.ndarray      # (R,)   real step count (<= padded T)
    sends_dst: jnp.ndarray    # (R, T*D) destination rank ids
    npkts: jnp.ndarray        # (R, T*D) packets per destination
    deg: jnp.ndarray          # (R, T) valid destinations per step
    recv_need: jnp.ndarray    # (R*T,) packets needed to complete a step
    total_sends: jnp.ndarray  # (R*T,) packets sent when a step is done
    sampled: jnp.ndarray      # (R, T*D) bool: sample destination?
    smp_lo: jnp.ndarray       # (R, T*D) sample range lo
    smp_hi: jnp.ndarray       # (R, T*D) sample range hi (exclusive)
    # fault epochs: NE >= 1 time-varying mask epochs (NE = 1 is a static
    # mask; padded epochs repeat the last mask and never start)
    link_ok: jnp.ndarray      # (NE, S, q*n) bool: healthy directed links
    mid_pool: jnp.ndarray     # (NE, S) healthy Valiant intermediates (cyclic)
    n_mid: jnp.ndarray        # (NE,) count of distinct healthy intermediates
    n_dead: jnp.ndarray       # (NE,) dead cables — sizes the deroute reserve
                              #     adaptive policies keep for fault escapes
    epoch_start: jnp.ndarray  # (NE,) int32 cycle each epoch begins; [0] == 0,
                              #     pad entries are INT32_MAX (never reached)

    @property
    def R(self) -> int:
        return self.rank_ep.shape[-1]

    @property
    def T(self) -> int:
        return self.deg.shape[-1]

    @property
    def D(self) -> int:
        return self.sends_dst.shape[-1] // self.deg.shape[-1]

    @property
    def NE(self) -> int:
        return self.epoch_start.shape[-1]

    @property
    def shape_bucket(self) -> tuple[int, int, int, int]:
        return (self.R, self.T, self.D, self.NE)


@dataclasses.dataclass(frozen=True)
class PreparedWorkload:
    """A workload lowered to device tables + the host-side metadata that
    the engine needs to interpret raw simulation outputs."""

    tables: WorkloadTables
    warmup: int        # makespan is reported relative to this time
    num_pools: int     # must match the engine's static pool count
    R: int             # real (unpadded) rank count
    T: int             # real (unpadded) step count
    NE: int = 1        # real (unpadded) fault-epoch count


def _pow2_bucket(x: int, floor: int = 1) -> int:
    b = max(floor, 1)
    while b < x:
        b *= 2
    return b


def shape_bucket(R: int, T: int, maxd: int) -> tuple[int, int, int]:
    """Pad (R, T, D) up to power-of-two buckets so near-miss shapes share
    one compilation (e.g. all-to-all T=63 and all-reduce T=64 -> T_b=64)."""
    return _pow2_bucket(R, 8), _pow2_bucket(T, 4), _pow2_bucket(maxd, 1)


def make_workload_tables(
    wl: Workload,
    bucket: bool = True,
    pack_tables: bool = True,
) -> PreparedWorkload:
    """Lower a :class:`Workload` into padded device tables.

    ``pack_tables`` (default) stores every small-range table in the
    narrowest dtype its **bucket-derived** bound admits (rank ids bound by
    R_b, endpoint ids by E, step counts by T_b, ...), so dtypes are a
    function of the shape bucket alone — packed tables stack and share
    compilations exactly like the int32 reference layout, and the step
    kernel widens at each gather, keeping results bit-identical
    (hypothesis-pinned).  ``pack_tables=False`` produces the int32
    reference used by the parity tests.
    """
    R, T, D = wl.R, wl.T, wl.maxd
    R_b, T_b, D_b = shape_bucket(R, T, D) if bucket else (R, T, D)
    E = wl.topo.num_endpoints

    def pad_r(a: np.ndarray, fill=0):
        if R_b == R:
            return a
        out = np.full((R_b,) + a.shape[1:], fill, dtype=a.dtype)
        out[:R] = a
        return out

    def pad_rtd(a: np.ndarray, fill=0):
        out = np.full((R_b, T_b, D_b), fill, dtype=a.dtype)
        out[:R, :T, :D] = a
        return out

    def pad_rt(a: np.ndarray, fill=0):
        out = np.full((R_b, T_b), fill, dtype=a.dtype)
        out[:R, :T] = a
        return out

    ep_rank = np.full(E, -1, dtype=np.int64)
    ep_rank[wl.rank_ep] = np.arange(R)

    n_steps = np.full(R_b, 0, dtype=np.int64)
    n_steps[:R] = T

    # pad ranks: infinite (ignored by completion) + no endpoint (never inject)
    infinite = pad_r(wl.infinite, fill=True)

    # fault mask + Valiant intermediate pool: topology-static shapes, so
    # fault scenarios share the shape bucket of their healthy counterparts.
    # A fault *schedule* stacks NE mask epochs along a leading axis; the
    # epoch count pads to a power of two (part of the shape bucket), with
    # pad epochs repeating the last mask at a start cycle no simulation
    # reaches.  NE = 1 (no schedule) keeps the static path.
    base_ok = wl.link_ok if wl.link_ok is not None else faults.no_faults(wl.topo)
    base_ok = np.asarray(base_ok, dtype=bool)
    sched = getattr(wl, "fault_schedule", None)
    if sched is None:
        epoch_start = np.zeros(1, dtype=np.int64)
        link_ok = base_ok[None]
    else:
        epoch_start = np.asarray(sched.epoch_start, dtype=np.int64)
        link_ok = np.asarray(sched.link_ok, dtype=bool) & base_ok[None]
    NE = len(epoch_start)
    NE_b = _pow2_bucket(NE, 1) if bucket else NE
    if NE_b > NE:
        _NEVER = np.iinfo(np.int32).max
        epoch_start = np.concatenate([
            epoch_start, np.full(NE_b - NE, _NEVER, dtype=np.int64)
        ])
        link_ok = np.concatenate([
            link_ok, np.repeat(link_ok[-1:], NE_b - NE, axis=0)
        ])
    valid_ports = self_port_mask(
        wl.topo.all_switch_coords(), wl.topo.n, wl.topo.q
    )
    mid_pool = np.empty((NE_b, wl.topo.num_switches), dtype=np.int32)
    n_mid = np.empty(NE_b, dtype=np.int64)
    n_dead = np.empty(NE_b, dtype=np.int64)
    for e in range(NE_b):
        mid_pool[e], n_mid[e] = faults.intermediate_pool(wl.topo, link_ok[e])
        dead_dirs = int((valid_ports & ~link_ok[e]).sum())
        n_dead[e] = (dead_dirs + 1) // 2  # cables (directed pairs, ceil)

    if pack_tables:
        # bucket-derived bounds only (R_b/T_b/D_b/E/S) — two same-bucket
        # workloads always pack to identical dtypes, so packed tables
        # stack and share compilations exactly like the int32 layout
        def lower(a, bound):
            return jnp.asarray(pack(a, bound))
    else:
        def lower(a, bound):
            return jnp.asarray(a, dtype=I32)

    # the window only acts through min(n_steps, completed + window) with
    # n_steps <= T_b, so clamping to T_b is semantics-free and gives the
    # field a bucket-derived bound (applied to both layouts for parity)
    window = np.minimum(pad_r(wl.window, fill=1), T_b)

    tables = WorkloadTables(
        rank_ep=lower(pad_r(wl.rank_ep), E - 1),
        ep_rank=lower(ep_rank, R_b),
        pool=lower(pad_r(wl.pool), max(wl.num_pools - 1, 0)),
        finite=jnp.asarray(~infinite),
        window=lower(window, T_b),
        start_t=jnp.asarray(pad_r(wl.start), dtype=I32),
        n_steps=lower(n_steps, T_b),
        sends_dst=lower(
            pad_rtd(wl.sends_dst, fill=-1).reshape(R_b, T_b * D_b), R_b
        ),
        npkts=jnp.asarray(pad_rtd(wl.npkts).reshape(R_b, T_b * D_b), dtype=I32),
        deg=lower(pad_rt(wl.deg), D_b),
        recv_need=jnp.asarray(pad_rt(wl.recv_need).reshape(R_b * T_b), dtype=I32),
        total_sends=jnp.asarray(
            pad_rt(wl.total_sends).reshape(R_b * T_b), dtype=I32
        ),
        sampled=jnp.asarray(pad_rtd(wl.sampled.astype(bool)).reshape(R_b, T_b * D_b)),
        smp_lo=lower(pad_rtd(wl.lo).reshape(R_b, T_b * D_b), R_b),
        smp_hi=lower(pad_rtd(wl.hi).reshape(R_b, T_b * D_b), R_b),
        link_ok=jnp.asarray(link_ok),
        mid_pool=lower(mid_pool, wl.topo.num_switches - 1),
        n_mid=jnp.asarray(n_mid, dtype=I32),
        n_dead=jnp.asarray(n_dead, dtype=I32),
        epoch_start=jnp.asarray(epoch_start, dtype=I32),
    )
    return PreparedWorkload(
        tables=tables, warmup=int(wl.start.max()), num_pools=wl.num_pools,
        R=R, T=T, NE=NE,
    )


def stack_tables(tables: Sequence[WorkloadTables]) -> WorkloadTables:
    """Stack same-bucket tables along a new leading batch axis (for vmap)."""
    buckets = {t.shape_bucket for t in tables}
    if len(buckets) != 1:
        raise ValueError(
            f"cannot stack workload tables from different shape buckets: "
            f"{sorted(buckets)}"
        )
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *tables)
