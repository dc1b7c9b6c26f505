"""Kind ``sweep``: the paper's interference grid (Table 4 / Fig. 8).

One ``SimEngine.run_grid`` call per step of a closed loop with one caller,
over one target job per allocation strategy (base block 0), with or
without a random-permutation background on every free endpoint.  The
mix's keys:

  * ``strategies``, ``kernel``, ``ranks``: the target job of each lane;
  * ``background``: ``{"pattern", "packets", "seed"}``, or ``null`` (or
    absent) for an isolated job;
  * ``sim_seeds``, ``seeds_per_call`` (default 1): call ``i`` runs the
    simulation seeds ``sim_seeds[(i * k + s) mod len(sim_seeds)]`` for
    ``s < k``, so a call has ``len(strategies) * k`` lanes;
  * ``warmup``, ``horizon``: cycles before the target job starts, and the
    loop's cap;
  * ``check_lanes``: how many of the window's lanes the check compares;
  * ``about``: a description, read by nobody.

The background uses the mix's seed and the random strategies' placements
the allocation seed 0, as the paper's harness does.  So every run does
the same work, and the run's ``--seed`` only orders the lanes of each
call and draws the lanes the check compares: a lane's makespan is an
outcome of the simulation, and work that moved with the seed would move
the rate by more than the timing noise.
"""

from __future__ import annotations

import hashlib
import time

import numpy as np

import generator

MIX_KEYS = {"kind", "about", "strategies", "kernel", "ranks", "background",
            "sim_seeds", "seeds_per_call", "warmup", "horizon",
            "check_lanes"}


class Cell:
    def __init__(self, config: dict, mix: dict, seed: int):
        generator.refuse_unknown("sweep mix", mix, MIX_KEYS)
        # the check needs a reference of this configuration: refuse early
        self.mc = self.reference_machine(config)
        self.config, self.mix, self.seed = config, mix, int(seed)
        order = np.random.default_rng([self.seed % 2**32, 1]).permutation(
            len(mix["strategies"]))
        self.strategies = [mix["strategies"][k] for k in order]
        self.horizon = int(mix["horizon"])
        self.warmup = int(mix["warmup"])
        self.background = mix.get("background")
        self.sim_seeds = [int(s) for s in mix["sim_seeds"]]
        self.per_call = int(mix.get("seeds_per_call", 1))
        self.engine = None
        # so is a kernel or a background it does not model
        self.lane(0)

    def call_seeds(self, i: int) -> list[int]:
        k = self.per_call
        return [self.sim_seeds[(i * k + s) % len(self.sim_seeds)]
                for s in range(k)]

    # ------------------------------------------------------------ program
    def _workload(self, strategy: str):
        from repro.traffic import (AppSpec, BackgroundSpec, PhaseSpec,
                                   ScenarioSpec, build_workload)

        bg = self.background
        spec = ScenarioSpec(
            apps=(AppSpec(phases=PhaseSpec(self.mix["kernel"]),
                          placement=strategy, ranks=self.mix["ranks"]),),
            background=None if bg is None else BackgroundSpec(
                pattern=bg["pattern"], packets=bg["packets"],
                seed=bg["seed"]),
            fabric_partitioning=self.config["fabric_partitioning"],
            warmup=self.warmup,
        )
        return build_workload(self._topo, spec)

    def setup(self) -> dict:
        from repro.core.engine import get_engine
        from repro.core.hyperx import HyperX

        t0 = time.perf_counter()
        topo = self.config["topology"]
        self._topo = HyperX(n=topo["n"], q=topo["q"],
                            concentration=topo["concentration"])
        self.workloads = [self._workload(s) for s in self.strategies]
        pools = {w.num_pools for w in self.workloads}
        if len(pools) != 1:
            raise ValueError(f"workloads need mixed VC pool counts {pools}")
        t1 = time.perf_counter()
        self.engine = get_engine(self._topo, mode=self.config["routing"],
                                 num_pools=pools.pop(),
                                 **self.config["engine"])
        t2 = time.perf_counter()
        # horizon is a traced argument: one cycle warms the same executable
        self.engine.run_grid(self.workloads, seeds=self.call_seeds(0),
                             horizon=1)
        t3 = time.perf_counter()
        h = hashlib.sha256()
        for w in self.workloads:
            for a in (w.rank_ep, w.pool, w.infinite, w.sends_dst, w.npkts,
                      w.deg, w.recv_need, w.sampled, w.lo, w.hi, w.window,
                      w.start):
                h.update(np.ascontiguousarray(a).tobytes())
        return {
            "traffic_sha256": h.hexdigest()[:16],
            "lanes_per_call": len(self.workloads) * self.per_call,
            "compile_keys": self.engine.bucket_stats()["misses"],
            "traces": self.engine.trace_count,
            "inputs_s": round(t1 - t0, 3),
            "engine_s": round(t2 - t1, 3),
            "warm_call_s": round(t3 - t2, 3),
        }

    def call(self, i: int) -> generator.CallRecord:
        res = self.engine.run_grid(self.workloads, seeds=self.call_seeds(i),
                                   horizon=self.horizon)
        answers = [((i, j, s), r) for j, per_seed in enumerate(res)
                   for s, r in enumerate(per_seed)]
        return record(i, [r.makespan for _, r in answers],
                      [r.completed for _, r in answers], self.warmup,
                      answers)

    def release(self):
        self.engine = None

    # ---------------------------------------------------------- reference
    # A kind of its own that models more (another routing, faults) can
    # subclass this Cell (``generator.load_kind("sweep").Cell``) and
    # override ``reference_machine``, ``lane`` and ``reference``.
    def reference_machine(self, config: dict):
        from reference.hyperx_sim import machine_from_config

        return machine_from_config(config)

    def lane(self, j: int):
        from reference.traffic import interference_lane

        return interference_lane(
            self.strategies[j], self.mc.n, self.mc.conc, self.mix["kernel"],
            self.mix["ranks"], 0, self.background, self.warmup)

    def sample(self, keys: list) -> list:
        """The lanes to check: ``check_lanes`` of them, drawn from the seed."""
        rng = np.random.default_rng([self.seed % 2**32, 2])
        k = min(int(self.mix["check_lanes"]), len(keys))
        return [keys[i] for i in sorted(rng.choice(len(keys), k,
                                                   replace=False))]

    def reference(self, key, break_link_rate: bool = False, draws=None):
        from reference.hyperx_sim import simulate

        i, j, s = key
        return simulate(self.mc, self.lane(j), self.call_seeds(i)[s],
                        self.horizon, break_link_rate=break_link_rate,
                        draws=draws)

    def check(self, calls: list) -> tuple[dict, dict]:
        from reference.hyperx_sim import Draws

        answers = dict(a for c in calls for a in c.answers)
        keys = self.sample(sorted(answers))
        draws = Draws(self.mc.NQ, self.mc.QN)
        bad = 0
        for key in keys:
            bad += generator.mismatches(generator.fields_of(answers[key]),
                                        self.reference(key, draws=draws))
        return ({"fields_mismatched": (bad, 0)},
                {"lanes_compared": len(keys),
                 "fields_per_lane": len(generator.FIELDS)})


def record(i, makespans, completed, warmup, answers) -> generator.CallRecord:
    # a lane's own cycles: warm-up + makespan, or the horizon
    own = [m + warmup for m in makespans]
    return generator.CallRecord(
        index=i, lanes=len(own), own_cycles=sum(own), iterations=max(own),
        failed=sum(not c for c in completed), answers=answers)


class Control(Cell):
    """The cell with its calls answered by the plain reference, the
    link-rate guarantee broken (an output port's tokens are never charged,
    so a link may carry two packets every packet-time)."""

    def setup(self):
        from reference.hyperx_sim import Draws

        self._draws = Draws(self.mc.NQ, self.mc.QN)
        return {}

    def call(self, i):
        answers = [((i, j, s), self.reference((i, j, s), break_link_rate=True,
                                              draws=self._draws))
                   for j in range(len(self.strategies))
                   for s in range(self.per_call)]
        return record(i, [a["makespan"] for _, a in answers],
                      [a["completed"] for _, a in answers], self.warmup,
                      answers)
