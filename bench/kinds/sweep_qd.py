"""Kind ``sweep_qd``: the interference sweep of kind ``sweep`` on a HyperX
of any dimension.

The mix's keys, the closed loop and the check are ``sweep``'s.  What
differs:

  * the reference lowers each lane with the q-D allocation forms
    (``reference/placement_qd.py``) on the machine's n**q * conc
    endpoints, and refuses a machine whose queue heads per lane do not fit
    the 17-bit head field of the packed arbitration key;
  * set-up compares each lane's endpoints, as the program placed them,
    with the reference's before anything is compiled, so a program that
    places the job elsewhere fails at once rather than after the window;
  * each call record carries the engine's queue heads per lane
    (``heads``), read by ``loop_ns_per_head_cycle.sweep_qd``; ``None``
    where the program does not say.
"""

import dataclasses
import time

import numpy as np

import generator

sweep = generator.load_kind("sweep")

HEAD_BITS = 17  # packed key: random bits << 17 | head index


@dataclasses.dataclass
class CallRecord(generator.CallRecord):
    heads: int | None = None   # queue heads per lane the call simulated


class Cell(sweep.Cell):
    def reference_machine(self, config: dict):
        mc = super().reference_machine(config)
        if mc.NQ >= 1 << HEAD_BITS:
            raise ValueError(f"{mc.NQ} queue heads per lane overflow the "
                             f"{HEAD_BITS}-bit head field of the packed "
                             f"arbitration key")
        return mc

    def lane(self, j: int):
        return self._lane(self.strategies[j])

    def _lane(self, strategy: str):
        from reference.placement_qd import interference_lane

        return interference_lane(
            strategy, self.mc.n, self.mc.q, self.mc.conc, self.mix["kernel"],
            self.mix["ranks"], 0, self.background, self.warmup)

    def _workload(self, strategy: str):
        w = super()._workload(strategy)
        want = self._lane(strategy).rank_ep
        if not np.array_equal(w.rank_ep, want):
            bad = int((w.rank_ep[:len(want)] != want[:len(w.rank_ep)]).sum())
            raise ValueError(
                f"the program places {strategy!r} on {bad} endpoints other "
                f"than the reference's q-D form on this {self.mc.n}^"
                f"{self.mc.q} machine")
        return w

    def call(self, i: int) -> CallRecord:
        rec = super().call(i)
        fields = {f.name: getattr(rec, f.name)
                  for f in dataclasses.fields(rec)}
        return CallRecord(**fields,
                          heads=getattr(self.engine, "heads_per_lane", None))

    def check(self, calls: list) -> tuple[dict, dict]:
        t0 = time.perf_counter()
        numbers, facts = super().check(calls)
        facts["reference_s_per_lane"] = round(
            (time.perf_counter() - t0) / max(facts["lanes_compared"], 1), 3)
        return numbers, facts


class Control(sweep.Control, Cell):
    """``sweep``'s control (the link-rate guarantee broken) on the q-D
    lowering."""
