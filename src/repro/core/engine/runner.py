"""SimEngine: compile-once, run-many execution of the cycle simulator.

The engine splits a simulation into

  * static structure (:mod:`tables`) — baked into one jitted step/while-loop
    per configuration, shared by every workload;
  * per-workload device data (:mod:`workload_tables`) — passed as pytree
    arguments, so the jit cache keys only on shape buckets.

``run_grid`` runs a workload x seed cross product: same-bucket tables
are stacked and the entire ``lax.while_loop`` is vmapped, so a whole
strategy x seed sweep is **one compilation and one device call** (per
shape bucket).  On one device the grid is a nested vmap (seeds
broadcast, tables never replicated); on more, it is flattened into a
*lane* axis sharded across every local device (``jax.shard_map`` over a
1-D mesh) — lanes are embarrassingly parallel, so an N-device host runs
an N-times-wider grid at the same wall-clock per bucket.  ``run`` is the
1 x 1 grid.

Engines are memoised by :func:`get_engine`; ``trace_count`` /
``device_calls`` expose how many XLA traces and dispatches actually
happened (the benchmark suite and the trace-counter tests assert on them).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from repro.core.engine.step import SimState, all_done, build_step, init_state
from repro.core.engine.tables import build_static_tables
from repro.core.engine.workload_tables import (
    PreparedWorkload,
    WorkloadTables,
    make_workload_tables,
    stack_tables,
)
from repro.core.hyperx import HyperX
from repro.obs import probes as obs_probes
from repro.obs import trace as obs_trace
from repro.obs.probes import Telemetry, TelemetrySpec, init_telemetry
from repro.route import get_policy
from repro.traffic.workload import Workload

PACKET_FLITS = 16  # paper Table 2: packet size 16 flits


def default_lane_backend(ndev: int | None = None) -> str:
    """The lane dispatcher :meth:`SimEngine.run_grid` will use on this host.

    Resolved at engine construction (and by the run manifest), not lazily
    at the first grid call: ``"vmap"`` on a single device, else
    ``"shard_map"``.
    """
    if ndev is None:
        ndev = jax.local_device_count()
    return "vmap" if ndev == 1 else "shard_map"


def _index_outs(outs, idx):
    """Index every leaf of a core-output pytree along the leading axis.

    Outputs are a tuple of arrays plus, for telemetry-enabled engines, a
    trailing :class:`TelemetryState` — tree indexing keeps both shapes
    uniform across the vmap/shard_map batching layouts.
    """
    return jax.tree_util.tree_map(lambda x: x[idx], outs)


@dataclasses.dataclass(frozen=True)
class SimResult:
    makespan: int             # packet-times until all target ranks completed
    makespan_cycles: int      # flit-cycles (x packet size)
    delivered: int            # target packets delivered
    injected: int             # packets injected (targets + background)
    avg_latency: float        # packet-times, target packets
    avg_hops: float           # network hops per delivered target packet
    completed: bool           # all target ranks finished within horizon
    max_hops: int = 0         # max hops over all ejected packets — must stay
                              # below the policy's VC budget (deadlock bound)
    # resilience accounting (defaults keep pre-epoch pickles comparable)
    reescalated: int = 0      # moves granted via forced fault-escape deroutes
    stranded: int = 0         # packets still queued in-network at the horizon
    ejected: int = 0          # packets ejected anywhere (injected - stranded)
    epoch_delivered: tuple = ()   # (NE,) target deliveries per fault epoch
    epoch_injected: tuple = ()    # (NE,) injections per fault epoch
    # windowed in-sim time series (engines built with a TelemetrySpec
    # only); excluded from equality so telemetry-on results still compare
    # against telemetry-off results on the simulated fields
    telemetry: Telemetry | None = dataclasses.field(
        default=None, compare=False, repr=False,
    )


class SimEngine:
    """Pytree-parameterized simulator for one static configuration.

    One engine == one ``(topo, mode, num_pools, max_deroutes, cap,
    penalty)`` tuple; ``mode`` resolves through the :mod:`repro.route`
    policy registry (``available_policies()`` lists valid names).  All
    workloads run through the same jitted core; re-tracing happens only
    when a workload's shape *bucket* is new — fault masks and Valiant
    intermediate pools are per-workload device data, so routing x
    strategy x fault grids batch like any other scenario axis.
    """

    def __init__(
        self,
        topo: HyperX,
        mode: str = "omniwar",
        num_pools: int = 1,
        max_deroutes: int | None = None,
        cap: int = 8,
        penalty_packets: int = 4,
        bucket: bool = True,
        arb: str = "lax",
        pack: bool = True,
        telemetry: TelemetrySpec | None = None,
        kernel: str = "lax",
        chunk: int = 1,
    ):
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self.topo = topo
        self.mode = mode
        self.policy = get_policy(mode)  # registry: unknown modes raise here
        self.num_pools = num_pools
        self.bucket = bucket
        self.pack = pack
        self.telemetry = telemetry
        self.kernel = kernel
        self.chunk = chunk
        self.static = build_static_tables(
            topo, mode=mode, num_pools=num_pools, max_deroutes=max_deroutes,
            cap=cap, penalty_packets=penalty_packets, arb=arb,
            pack_tables=pack, kernel=kernel,
        )
        self._step = build_step(self.static, telemetry=telemetry)
        self.trace_count = 0   # XLA traces of the core (any batching)
        self.device_calls = 0  # jitted dispatches issued
        self.bucket_hits = 0   # dispatches whose compile key was seen before
        self.bucket_misses = 0  # dispatches that opened a new compile key
        self._seen_keys: set = set()

        if chunk == 1:
            # cycle-granular reference loop: `all_done` checked every cycle
            loop = jax.lax.while_loop
        else:
            def loop(cond, body, init):
                # while-of-scan chunks: the `all_done` reduction runs every
                # `chunk` cycles and XLA fuses across cycles within a chunk
                # (scan/while carries are buffer-donated by XLA, so the
                # chunk adds no copies).  Result-exact for any K: `cond` is
                # monotone (horizon and completion only latch one way), so
                # freezing the carry on the first inactive cycle makes the
                # in-chunk tail a no-op and records the exact completion
                # cycle — the fixed point is the while_loop's, bit for bit.
                def cstep(carry, _):
                    active = cond(carry)
                    new = body(carry)
                    return jax.tree_util.tree_map(
                        lambda old, upd: jnp.where(active, upd, old),
                        carry, new,
                    ), None

                def chunk_body(carry):
                    carry, _ = jax.lax.scan(cstep, carry, None, length=chunk)
                    return carry

                return jax.lax.while_loop(cond, chunk_body, init)

        if telemetry is None:
            def core(wt: WorkloadTables, seed, horizon):
                # Python side effect: runs once per trace, never per call.
                self.trace_count += 1

                def cond(state: SimState):
                    return (state.t < horizon) & ~all_done(wt, state)

                def body(state: SimState):
                    return self._step(state, wt)

                final = loop(cond, body, init_state(self.static, wt, seed))
                return (
                    final.t, all_done(wt, final), final.n_delivered,
                    final.n_injected, final.lat_sum, final.hop_sum,
                    final.hop_max, final.esc_count, jnp.sum(final.qlen),
                    final.epoch_delivered, final.epoch_injected,
                )
        else:
            st = self.static

            def core(wt: WorkloadTables, seed, horizon):
                self.trace_count += 1

                def cond(carry):
                    state, _ = carry
                    return (state.t < horizon) & ~all_done(wt, state)

                def body(carry):
                    return self._step(carry, wt)

                init = (
                    init_state(st, wt, seed),
                    init_telemetry(telemetry, st.S, st.OUT, st.P, st.CAP),
                )
                final, tel = loop(cond, body, init)
                return (
                    final.t, all_done(wt, final), final.n_delivered,
                    final.n_injected, final.lat_sum, final.hop_sum,
                    final.hop_max, final.esc_count, jnp.sum(final.qlen),
                    final.epoch_delivered, final.epoch_injected, tel,
                )

        self._core = core
        # (workloads x seeds) cross product: tables batch on the outer axis
        # only, seeds broadcast on the inner — no per-seed table replication
        self._runNS = jax.jit(jax.vmap(
            jax.vmap(core, in_axes=(None, 0, None)),
            in_axes=(0, None, None),
        ))
        self._lane_runner = None       # built lazily (multi-device only)
        # resolved at construction on every host shape (the run manifest
        # records it)
        self.lane_backend = default_lane_backend()

    # ------------------------------------------------------------- prepare
    def prepare(self, wl: Workload | PreparedWorkload) -> PreparedWorkload:
        """Lower a Workload to padded device tables (idempotent)."""
        if isinstance(wl, PreparedWorkload):
            prep = wl
        else:
            if wl.topo != self.topo:
                raise ValueError(
                    f"workload was composed on {wl.topo} but engine was "
                    f"built for {self.topo}"
                )
            prep = make_workload_tables(
                wl, bucket=self.bucket, pack_tables=self.pack
            )
        if prep.num_pools != self.num_pools:
            raise ValueError(
                f"workload uses {prep.num_pools} VC pools but engine was "
                f"built with num_pools={self.num_pools}"
            )
        return prep

    def _note_bucket(self, fn: str, bucket, dims: tuple) -> bool:
        """Account one dispatch against the compile-key it lands on;
        returns whether the key is new.

        ``(fn, shape bucket, batch dims)`` mirrors the jit cache key of
        the dispatched callable — a *miss* is a dispatch that opens a new
        key (first trace+compile), a *hit* reuses one.  The hit rate is
        the compile-amortization figure of merit ``benchmarks/perf.py``
        records in ``BENCH_*.json``.
        """
        key = (fn, bucket, dims)
        if key in self._seen_keys:
            self.bucket_hits += 1
            return False
        self.bucket_misses += 1
        self._seen_keys.add(key)
        return True

    def bucket_stats(self) -> dict:
        """Compile-key hit/miss counters for this engine's dispatches."""
        total = self.bucket_hits + self.bucket_misses
        return {
            "hits": self.bucket_hits,
            "misses": self.bucket_misses,
            "hit_rate": (self.bucket_hits / total) if total else 0.0,
        }

    @property
    def heads_per_lane(self) -> int:
        """Queue heads the cycle loop serves per lane and cycle: H = S *
        IN * P * V, the length of every per-head array of the step."""
        return self.static.H

    @property
    def head_bits(self) -> int:
        """Width of the packed arbitration key's head field: 17 below 2**17
        heads a lane, else the least that holds them (``tables.py``)."""
        return self.static.head_bits

    # ------------------------------------------------------------ running
    def run(
        self,
        wl: Workload | PreparedWorkload,
        seed: int = 0,
        horizon: int = 60_000,
    ) -> SimResult:
        """One workload, one seed: the grid's nested-vmap cross product at
        1 x 1 on any device count (one lane has nothing to shard)."""
        return self._run_lanes("run", [wl], [int(seed)], horizon,
                               sharded=False)[0][0]

    def run_grid(
        self,
        workloads: Sequence[Workload | PreparedWorkload],
        seeds: Sequence[int] | None = None,
        horizon: int = 60_000,
    ) -> list[list[SimResult]]:
        """Run the workload x seed cross product, one device call per
        shape bucket; results come back as ``results[workload][seed]`` in
        input order (``seeds`` defaults to ``[0]``).

        On one device the grid runs as a nested vmap: tables batch on the
        workload axis, seeds broadcast on the inner one, so nothing is
        replicated per seed.  On more, it is flattened into a *lane* axis
        (one lane per (workload, seed) pair) and sharded with
        ``jax.shard_map`` over a 1-D device mesh; lanes are padded
        round-robin to a multiple of the device count, so every device
        receives equal work, and padded lanes are computed and discarded.
        Results are bitwise identical on every backend (lane flattening
        only re-associates the batch axes); ``self.lane_backend`` records
        which layout runs.  A zip of per-workload seeds is the diagonal,
        ``[r[i] for i, r in enumerate(grid)]``.
        """
        seeds = [0] if seeds is None else [int(s) for s in seeds]
        return self._run_lanes("run_grid", workloads, seeds, horizon,
                               sharded=jax.local_device_count() > 1)

    def _make_lane_runner(self):
        """Build the multi-device lane dispatcher (``jax.shard_map``).

        Lanes — flattened (workload, seed) pairs with stacked tables — are
        embarrassingly parallel, so the dispatcher just splits the lane
        axis across devices and vmaps within each shard.  Tracing still
        happens once per shape bucket (SPMD), which the trace-counter
        tests pin.
        """
        mesh = Mesh(np.asarray(jax.devices()), ("lanes",))
        return jax.jit(jax.shard_map(
            jax.vmap(self._core, in_axes=(0, 0, None)),
            mesh=mesh,
            in_specs=(PartitionSpec("lanes"), PartitionSpec("lanes"), None),
            out_specs=PartitionSpec("lanes"),
            check_vma=False,
        ))

    # ------------------------------------------------------------ private
    # Host stages of a call, each an ``obs.trace.stage`` span (on the
    # profiler's clock, and in the JSONL log while a tracer is active):
    # engine.prepare -> engine.stack -> engine.dispatch -> engine.to_result.
    def _run_lanes(self, api, workloads, seeds, horizon, sharded):
        """The workload x seed cross product, one dispatch per shape
        bucket, on the nested vmap or (``sharded``) the shard_map lanes."""
        with obs_trace.stage("engine.prepare", api=api,
                             workloads=len(workloads)):
            preps = [self.prepare(w) for w in workloads]
        groups: dict[tuple[int, int, int, int], list[int]] = {}
        for i, p in enumerate(preps):
            groups.setdefault(p.tables.shape_bucket, []).append(i)
        results: list[list[SimResult]] = [[None] * len(seeds)  # type: ignore
                                          for _ in preps]
        for idxs in groups.values():
            bucket = preps[idxs[0]].tables.shape_bucket
            lanes = [(i, k) for i in idxs for k in range(len(seeds))]
            if sharded:
                if self._lane_runner is None:
                    self._lane_runner = self._make_lane_runner()
                pad = -len(lanes) % jax.local_device_count()
                lanes_p = lanes + [lanes[k % len(lanes)] for k in range(pad)]
                stacked, seed_arr = self._stack(
                    preps, [i for i, _ in lanes_p],
                    [seeds[k] for _, k in lanes_p],
                )
                runner, fn, dims = self._lane_runner, "lanes", (len(lanes_p),)
            else:
                stacked, seed_arr = self._stack(preps, idxs, seeds)
                runner, fn, dims = self._runNS, "runNS", (len(idxs),
                                                          len(seeds))
            outs = self._dispatch(api, runner, fn, bucket, dims, len(lanes),
                                  stacked, seed_arr, horizon)
            with self._results_span(outs, len(lanes)):
                for lane, (i, k) in enumerate(lanes):
                    at = lane if sharded else (lane // len(seeds), k)
                    results[i][k] = self._to_result(_index_outs(outs, at),
                                                    preps[i])
        return results

    def _stack(self, preps, idxs, seeds) -> tuple[WorkloadTables, jax.Array]:
        """The stacked tables of ``preps[idxs]`` and the seed array."""
        with obs_trace.stage("engine.stack", tables=len(idxs)):
            return (stack_tables([preps[i].tables for i in idxs]),
                    jnp.asarray(seeds, dtype=jnp.int32))

    def _dispatch(self, api, runner, fn, bucket, dims, lanes, tables, seeds,
                  horizon):
        """One device dispatch, waited for: the ``engine.dispatch`` span
        covers the enqueue and the device run up to its last output
        (``_to_result`` would wait there anyway), so it times the device
        call.  ``lanes`` counts the lanes asked for, ``padded_lanes`` the
        lanes run (``dims``, the batch axes); ``new_key`` marks the first
        dispatch of a compile key, so a compile shows on the profiler's
        clock.  An ``engine.compile`` event follows a dispatch that traced
        new code while a tracer is active."""
        self.device_calls += 1
        new_key = self._note_bucket(fn, bucket, dims)
        traces0 = self.trace_count
        with obs_trace.stage(
            "engine.dispatch", api=api, mode=self.mode, lanes=lanes,
            padded_lanes=int(np.prod(dims)), bucket=str(bucket),
            new_key=new_key, backend=self.lane_backend,
            heads=self.heads_per_lane, switches=self.static.S,
            head_bits=self.head_bits, net_ports=self.static.q * self.static.n,
        ):
            outs = jax.block_until_ready(
                runner(tables, seeds, jnp.int32(horizon))
            )
        if self.trace_count > traces0:
            obs_trace.event("engine.compile", api=api, mode=self.mode,
                            traces=self.trace_count - traces0)
        return outs

    def _results_span(self, outs, lanes: int):
        """The ``engine.to_result`` span: ``lanes`` results, each of
        ``arrays`` device arrays brought to the host."""
        arrays = lanes * len(jax.tree_util.tree_leaves(outs))
        return obs_trace.stage("engine.to_result", lanes=lanes,
                               arrays=arrays)

    def _to_result(self, out, prep: PreparedWorkload) -> SimResult:
        tel = None
        if self.telemetry is not None:
            out, tel_state = out[:11], out[11]
            tel = obs_probes.to_host(tel_state, self.telemetry, self.static)
        (t, done, ndel, ninj, lat, hops, hmax, esc, qsum, edel, einj) = (
            np.asarray(x) for x in out
        )
        ndel = int(ndel)
        return SimResult(
            makespan=int(t) - prep.warmup,
            makespan_cycles=(int(t) - prep.warmup) * PACKET_FLITS,
            delivered=ndel,
            injected=int(ninj),
            avg_latency=float(lat) / max(ndel, 1),
            avg_hops=float(hops) / max(ndel, 1),
            completed=bool(done),
            max_hops=int(hmax),
            reescalated=int(esc),
            stranded=int(qsum),
            ejected=int(ninj) - int(qsum),
            # pad epochs never start, so their counters are exact zeros;
            # trim to the real epoch count for the host view
            epoch_delivered=tuple(int(x) for x in edel[: prep.NE]),
            epoch_injected=tuple(int(x) for x in einj[: prep.NE]),
            telemetry=tel,
        )


@functools.lru_cache(maxsize=None)
def _engine_for(topo, mode, num_pools, max_deroutes, cap, penalty_packets,
                bucket, arb, pack, telemetry, kernel, chunk):
    return SimEngine(
        topo, mode=mode, num_pools=num_pools, max_deroutes=max_deroutes,
        cap=cap, penalty_packets=penalty_packets, bucket=bucket, arb=arb,
        pack=pack, telemetry=telemetry, kernel=kernel, chunk=chunk,
    )


def get_engine(
    topo: HyperX,
    mode: str = "omniwar",
    num_pools: int = 1,
    max_deroutes: int | None = None,
    cap: int = 8,
    penalty_packets: int = 4,
    bucket: bool = True,
    arb: str = "lax",
    pack: bool = True,
    telemetry: TelemetrySpec | None = None,
    kernel: str = "lax",
    chunk: int = 1,
) -> SimEngine:
    """Memoised engine lookup: one engine (and one compile) per config.

    Arguments are normalised into one positional cache key, so calls that
    spell defaults explicitly share the engine with calls that omit them.
    ``arb`` selects the switch-arbitration backend ("lax" | "pallas", bit
    identical); ``kernel`` selects the route+arbitrate implementation
    ("lax" | "pallas" fused megakernel, bit identical); ``chunk`` is the
    early-exit granularity of the cycle loop (K cycles per ``all_done``
    check — result-exact for any K, K=1 is the cycle-granular reference);
    ``pack`` controls int8/int16 table packing (default on —
    ``False`` is the int32 reference layout for parity tests).
    ``telemetry`` (a hashable :class:`~repro.obs.probes.TelemetrySpec`)
    is part of the key: enabling probes builds a separate engine, leaving
    every default-keyed consumer on the untouched kernel.
    """
    return _engine_for(
        topo, mode, num_pools, max_deroutes, cap, penalty_packets, bucket,
        arb, pack, telemetry, kernel, chunk,
    )
