"""Machine-state ledger: block slots + endpoints on a fragmented machine.

The paper's allocation functions tessellate a well-balanced n x n HyperX
into exactly ``n`` disjoint *base blocks* (partition ids p in [0, n)), so
the natural scheduling granularity is the block slot.  The ledger keeps
**endpoint-level occupancy as ground truth** (a bool per endpoint, exactly
like :class:`~repro.core.allocation.JobAllocator`), and derives per-strategy
slot views from it: block slot ``p`` of strategy ``S`` is free iff every
endpoint that ``S`` maps into block ``p`` is free and healthy.  Because the
views are derived, jobs placed under *different* strategies can safely
coexist on one machine (their block frames differ, but endpoint-level
disjointness is what is enforced and conserved).

Placement policies over block sets:

  * ``first_fit`` — lowest contiguous run of free slots that fits;
  * ``best_fit``  — smallest contiguous run that fits (ties: lowest);
  * both fall back to the lowest k free slots ("scatter") when no
    contiguous run fits and ``allow_scatter`` is set — the paper's
    consecutive-blocks convention is preferred but not required, and the
    realized-PB metrics quantify what scattering costs.

The API is a superset of :class:`JobAllocator`'s surface (``allocate`` /
``release`` / ``fail_endpoints`` / ``repair_endpoints`` / ``capacity`` plus
``free``/``failed``/``jobs``/``seed``), so the ledger drops into
:class:`repro.runtime.FleetRuntime` as the fleet allocator and the repair
path goes through :meth:`replace_job`.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

from repro.core.allocation import (
    AllocationStrategy,
    Partition,
    allocate_blocks,
    get_strategy,
    scavenge_partition,
)
from repro.core.hyperx import HyperX


@dataclasses.dataclass(frozen=True)
class PlacedJob:
    """Ledger record of one placed job."""

    partition: Partition
    slots: tuple[int, ...]       # block slots occupied, rank order
    slot_endpoints: np.ndarray   # ALL endpoints of those slots (>= size)
    contiguous: bool


def _runs(free: np.ndarray) -> list[tuple[int, int]]:
    """Maximal runs of True as (start, length), in index order."""
    out = []
    start = None
    for i, f in enumerate(free):
        if f and start is None:
            start = i
        elif not f and start is not None:
            out.append((start, i - start))
            start = None
    if start is not None:
        out.append((start, len(free) - start))
    return out


class BlockLedger:
    """Free/occupied block and endpoint tracking for one HyperX machine."""

    def __init__(
        self,
        topo: HyperX,
        strategy: str | AllocationStrategy = "diagonal",
        seed: int = 0,
        policy: str = "first_fit",
        allow_scatter: bool = True,
    ):
        if topo.concentration != topo.n:
            raise ValueError(
                f"block ledger needs a well-balanced machine "
                f"(concentration == n), got {topo}"
            )
        if topo.q != 2:
            raise ValueError(
                f"block ledger's slot views assume the n x n machine's n "
                f"block slots; a q={topo.q} HyperX ({topo}) is not "
                f"supported yet"
            )
        if policy not in ("first_fit", "best_fit"):
            raise ValueError(f"unknown placement policy {policy!r}")
        self.topo = topo
        self.strategy = get_strategy(strategy) if isinstance(strategy, str) else strategy
        self.seed = seed
        self.policy = policy
        self.allow_scatter = allow_scatter
        self.block = topo.n * topo.n
        self.num_slots = topo.n
        self.free = np.ones(topo.num_endpoints, dtype=bool)
        self.failed = np.zeros(topo.num_endpoints, dtype=bool)
        self.jobs: Dict[int, PlacedJob] = {}
        self._next_job = 0
        self._slot_eps: dict[tuple[str, int, int], np.ndarray] = {}

    # ------------------------------------------------------------ slot views
    def slot_endpoints(self, slot: int, strategy=None) -> np.ndarray:
        """All n**2 endpoints that ``strategy`` maps into block ``slot``.

        The cache is keyed by the *current* seed as well: FleetRuntime's
        stochastic fallback mutates ``allocator.seed`` between placements,
        and a view cached under another seed would disagree with what
        :func:`allocate_blocks` actually allocates."""
        strat = self._strat(strategy)
        key = (strat.name, self.seed, int(slot))
        eps = self._slot_eps.get(key)
        if eps is None:
            part = allocate_blocks(strat, self.topo, [int(slot)], seed=self.seed)
            eps = np.sort(part.endpoints)
            self._slot_eps[key] = eps
        return eps

    def free_slots(self, strategy=None) -> np.ndarray:
        """(n,) bool: slot fully free AND fully healthy under ``strategy``."""
        ok = np.empty(self.num_slots, dtype=bool)
        for p in range(self.num_slots):
            eps = self.slot_endpoints(p, strategy)
            ok[p] = bool(self.free[eps].all())
        return ok

    def capacity(self) -> int:
        return int(self.free.sum())

    def fragmentation(self, strategy=None) -> float:
        """1 - largest_free_run / free_slots (0 = contiguous, -> 1 = shredded).

        Measured in the block frame of ``strategy`` (default: the ledger's):
        a machine whose free slots cannot host a multi-block job contiguously
        forces either queueing or scattered placement.
        """
        free = self.free_slots(strategy)
        total = int(free.sum())
        if total == 0:
            return 0.0
        largest = max((ln for _, ln in _runs(free)), default=0)
        return 1.0 - largest / total

    # ------------------------------------------------------------- placement
    def find_slots(self, k: int, strategy=None) -> tuple[list[int], bool] | None:
        """Pick ``k`` free slots by policy; (slots, contiguous) or None."""
        if k <= 0:
            raise ValueError(f"need a positive block count, got {k}")
        free = self.free_slots(strategy)
        runs = [(s, ln) for s, ln in _runs(free) if ln >= k]
        if runs:
            if self.policy == "best_fit":
                start, _ = min(runs, key=lambda r: (r[1], r[0]))
            else:
                start, _ = runs[0]
            return list(range(start, start + k)), True
        if self.allow_scatter:
            idx = np.flatnonzero(free)
            if len(idx) >= k:
                return idx[:k].tolist(), False
        return None

    def place(
        self,
        blocks: int,
        size: int | None = None,
        strategy=None,
        job_id: int | None = None,
    ) -> Partition:
        """Place a job of ``blocks`` base blocks; raises RuntimeError if it
        does not fit.  ``size`` (endpoints, default blocks*n**2) may take a
        prefix of the final block; the whole slot is still held (internal
        fragmentation, exactly like node-granular HPC schedulers)."""
        strat = self._strat(strategy)
        found = self.find_slots(blocks, strat)
        if found is None:
            raise RuntimeError(
                f"no {blocks} free {strat.name} block(s) "
                f"(free endpoints: {self.capacity()}, "
                f"fragmentation: {self.fragmentation(strat):.2f})"
            )
        slots, contiguous = found
        jid = self._next_job if job_id is None else job_id
        if jid in self.jobs:
            raise ValueError(f"job id {jid} is already placed")
        part = allocate_blocks(
            strat, self.topo, slots, job_id=jid, size=size, seed=self.seed
        )
        slot_eps = np.concatenate([self.slot_endpoints(p, strat) for p in slots])
        assert self.free[slot_eps].all(), "ledger invariant: slots were free"
        self.free[slot_eps] = False
        self.jobs[jid] = PlacedJob(
            partition=part, slots=tuple(slots),
            slot_endpoints=slot_eps, contiguous=contiguous,
        )
        # keep auto ids clear of explicit ones (shared-ledger tenants)
        self._next_job = max(self._next_job, jid + 1)
        return part

    def allocate(self, size: int | None = None, strategy=None) -> Partition:
        """JobAllocator-compatible entry: size in endpoints, blocks = ceil."""
        size = size or self.block
        return self.place(-(-size // self.block), size=size, strategy=strategy)

    def scavenge(self, size: int) -> Partition:
        """Last-resort placement on arbitrary free endpoints (no block
        structure) — the FleetRuntime fallback contract.  Recorded with an
        empty slot list; the held endpoints are exactly the partition's."""
        part = scavenge_partition(self.free, self.topo, self._next_job, size)
        self.free[part.endpoints] = False
        self.jobs[part.job_id] = PlacedJob(
            partition=part, slots=(), slot_endpoints=part.endpoints,
            contiguous=False,
        )
        self._next_job += 1
        return part

    def release(self, job_id: int) -> None:
        job = self.jobs.pop(job_id)
        # failed endpoints stay out of the pool until repaired
        self.free[job.slot_endpoints] = ~self.failed[job.slot_endpoints]

    # ------------------------------------------------------ failure / repair
    def fail_endpoints(self, endpoints) -> list[int]:
        """Mark endpoints failed; return ids of jobs whose slots they hit."""
        endpoints = np.atleast_1d(np.asarray(endpoints, dtype=np.int64))
        affected = [
            jid for jid, job in self.jobs.items()
            if np.intersect1d(job.slot_endpoints, endpoints).size
        ]
        self.failed[endpoints] = True
        self.free[endpoints] = False
        return affected

    def repair_endpoints(self, endpoints) -> None:
        """Return repaired endpoints to the pool (unless currently held)."""
        endpoints = np.atleast_1d(np.asarray(endpoints, dtype=np.int64))
        self.failed[endpoints] = False
        held = np.zeros_like(self.free)
        for job in self.jobs.values():
            held[job.slot_endpoints] = True
        self.free[endpoints] = ~held[endpoints]

    def replace_job(self, job_id: int, strategy=None) -> Partition:
        """Re-place a job after failures hit its slots (the repair path).

        Releases the old slots and places the same block count on the
        surviving machine — same contract as FleetRuntime's repair: the
        caller restores application state from checkpoint onto the new
        partition.  Raises RuntimeError (with the job *unplaced* and its
        old slots released) when the survivors cannot host it.
        """
        old = self.jobs[job_id]
        self.release(job_id)
        return self.place(
            len(old.slots), size=old.partition.size,
            strategy=strategy, job_id=job_id,
        )

    # ------------------------------------------------------------ invariants
    def owner_map(self) -> np.ndarray:
        """(E,) job id holding each endpoint, -1 free/failed.  Raises on
        overlap (the disjointness invariant the tests pin)."""
        owner = np.full(self.topo.num_endpoints, -1, dtype=np.int64)
        for jid, job in self.jobs.items():
            if (owner[job.slot_endpoints] != -1).any():
                raise ValueError(f"ledger overlap at job {jid}")
            owner[job.slot_endpoints] = jid
        return owner

    def check_conservation(self) -> None:
        """free, held and failed-unheld endpoints must tile the machine."""
        owner = self.owner_map()  # raises on overlap
        held = owner != -1
        if (self.free & held).any():
            raise AssertionError("endpoint both free and held")
        if (self.free & self.failed).any():
            raise AssertionError("endpoint both free and failed")
        accounted = self.free | held | self.failed
        if not accounted.all():
            raise AssertionError(
                f"{int((~accounted).sum())} endpoints leaked from the ledger"
            )

    def _strat(self, strategy) -> AllocationStrategy:
        if strategy is None:
            return self.strategy
        return get_strategy(strategy) if isinstance(strategy, str) else strategy

    def __repr__(self) -> str:
        return (
            f"BlockLedger({self.topo}, {self.strategy.name}, "
            f"free={self.capacity()}/{self.topo.num_endpoints}, "
            f"jobs={len(self.jobs)})"
        )
