"""Resource allocation functions for HyperX networks (paper Section 4).

Each allocation function maps the logical coordinates of a job's rank onto
a physical switch and endpoint offset:

    f(p, r_y, r_x) = (switch coordinates, c)

where ``p`` is the partition identifier, ``r = n*r_y + r_x`` is the linear
rank inside the partition and ``c`` the endpoint offset within the switch.
A base block is n**2 endpoints, so a well-balanced q-D HyperX of side n
holds exactly n**(q-1) disjoint partitions; on the paper's n x n machine
that is n partitions and the coordinates are ``(s_y, s_x)``.

The paper defines the strategies in 2D.  Their q-D forms are this
repository's (DESIGN.md §4): write ``p`` in base n as digits
p_{q-2} .. p_0 (p_0 least significant); switch coordinates are listed
most significant first, as in :meth:`HyperX.switch_id`; every form
reduces to the paper's formula at q = 2, bit for bit.

  row:           (p_{q-2}, .., p_0, r_y); r_x       -- a line
  diagonal:      (r_y, r_y + p_{q-2}, .., r_y + p_0) mod n; r_x
  full_spread:   (p_{q-2}, .., p_1, r_y, r_x); p_0  -- a plane
  rectangular:   boxes of side 2 in every dimension but the last and
                 n / 2**(q-1) in the last
  l_shape:       the 2D L of partition p_0 in the plane of the last two
                 dimensions, at leading coordinates (p_{q-2}, .., p_1)
  random_endpoint, random_switch: a seeded permutation of the machine's
                 endpoints (switches), indexed by the linear rank

Implemented strategies (names follow the paper):

  linear:     row, diagonal, full_spread
  tiled:      rectangular, l_shape
  stochastic: random_endpoint, random_switch

Jobs larger than n**2 take the union of consecutive base blocks (paper
Section 6.2: "a partition consists on the union of consecutive blocks").

All ``map_block`` implementations are vectorized over numpy int arrays so the
simulator, the property analysis and the fabric placement layer can evaluate
them for thousands of ranks at once.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Sequence, Tuple

import numpy as np

from repro.core.hyperx import HyperX

# (switch id, endpoint offset) per rank
Placement = Tuple[np.ndarray, np.ndarray]


# --------------------------------------------------------------------------
# Strategy definitions
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class AllocationStrategy:
    """A named allocation function plus its static properties (paper Table 1)."""

    name: str
    kind: str  # 'linear' | 'tiling' | 'random'
    locality_aware: bool
    convexity: str  # 'convex' | 'weakly-convex' | 'non-convex'
    # map_block(p, r_y, r_x, n, q, rng) -> (switch, c); vectorized over arrays.
    map_block: Callable[..., Placement]
    needs_rng: bool = False

    def __call__(self, p, r_y, r_x, n, q=2, rng=None) -> Placement:
        p = np.asarray(p, dtype=np.int64)
        r_y = np.asarray(r_y, dtype=np.int64)
        r_x = np.asarray(r_x, dtype=np.int64)
        if self.needs_rng and rng is None:
            rng = np.random.default_rng(0)
        return self.map_block(p, r_y, r_x, n, q, rng)


def _digits(p, n, q):
    """Block id ``p`` as base-n digits (p_{q-2}, .., p_0)."""
    return [(p // n**i) % n for i in range(q - 2, -1, -1)]


def _switch(coords, n):
    """Linear switch id of coordinates listed most significant first."""
    s = 0
    for c in coords:
        s = s * n + c
    return s


def _row(p, r_y, r_x, n, q, rng):
    # row(p, r_y, r_x) = (p, r_y, r_x): all endpoints in row p.
    return _switch([*_digits(p, n, q), r_y % n], n), r_x % n


def _full_spread(p, r_y, r_x, n, q, rng):
    # full_spread(p, r_y, r_x) = (r_y, r_x, p): one endpoint on EVERY
    # switch (of the plane the leading digits select).
    *lead, p0 = _digits(p, n, q)
    return _switch([*lead, r_y % n, r_x % n], n), p0


def _diagonal(p, r_y, r_x, n, q, rng):
    # diagonal(p, r_y, r_x) = (r_y, (r_y + p) mod n, r_x): one switch per
    # line of the machine -- maximal distance, maximal partition bandwidth
    # among locality-aware strategies.
    r_y = r_y % n
    return _switch([r_y] + [(r_y + d) % n for d in _digits(p, n, q)], n), r_x % n


def _rectangular(p, r_y, r_x, n, q, rng):
    # Paper formula (Sec. 4.2):
    #   (rem(r_y,2) + n/2*rem(p,2), quo(r_y,2) + 2*quo(p,2), r_x)
    # As printed this yields OVERLAPPING rectangles (p=0 covers rows {0,1} x
    # cols {0..3}, p=2 covers rows {0,1} x cols {2..5}), contradicting the
    # paper's own claim of n non-overlapping partitions.  Swapping the two
    # offset terms gives the intended disjoint sqrt(n/2) x sqrt(2n) tiling
    # (2 rows x 4 cols for n=8); erratum recorded in DESIGN.md.  In q-D a
    # box has side 2 in every dimension but the last and n / 2**(q-1) in
    # the last; box p is mixed radix over the tile counts (last dimension
    # least significant), and r_y walks the first dimension fastest.
    if n % 2 ** (q - 1):
        raise ValueError(
            f"rectangular tessellation needs n divisible by 2**(q-1), "
            f"got n={n}, q={q}")
    sides = [2] * (q - 1) + [n // 2 ** (q - 1)]
    box = []
    for side in reversed(sides):
        box.append(p % (n // side))
        p = p // (n // side)
    coords = []
    for side, b in zip(sides, reversed(box)):
        coords.append((b * side + r_y % side) % n)
        r_y = r_y // side
    return _switch(coords, n), r_x % n


def _l_shape(p, r_y, r_x, n, q, rng):
    # Piecewise: a vertical ray anchored at (p, p) plus a horizontal ray.
    #   (p + r_y, p, r_x)                       for r_y <  n//2
    #   (p, p + r_y - n//2 + 1, r_x)            otherwise
    # Modular arithmetic applies to switch coordinates.  In q-D the L lies
    # in the plane of the last two dimensions.
    *lead, p0 = _digits(p, n, q)
    half = n // 2
    vert = r_y < half
    s_y = np.where(vert, (p0 + r_y) % n, p0)
    s_x = np.where(vert, p0, (p0 + r_y - half + 1) % n)
    return _switch([*lead, s_y, s_x], n), r_x % n


def _random_endpoint(p, r_y, r_x, n, q, rng):
    # pi is a random permutation of the n**(q+1) endpoint slots (switch,
    # offset < n); the linear rank index maps straight into it.
    size = n ** (q + 1)
    tgt = rng.permutation(size)[(p * n * n + r_y * n + r_x) % size]
    return tgt // n, tgt % n


def _random_switch(p, r_y, r_x, n, q, rng):
    # sigma is a random permutation of the n**q switches; r_y selects the
    # switch, r_x the endpoint offset -> switch locality preserved.
    size = n**q
    return rng.permutation(size)[(p * n + r_y) % size], r_x % n


ALLOCATIONS: Dict[str, AllocationStrategy] = {
    s.name: s
    for s in [
        AllocationStrategy("row", "linear", True, "convex", _row),
        AllocationStrategy("diagonal", "linear", True, "non-convex", _diagonal),
        AllocationStrategy("full_spread", "linear", False, "convex", _full_spread),
        AllocationStrategy("rectangular", "tiling", True, "convex", _rectangular),
        AllocationStrategy("l_shape", "tiling", True, "weakly-convex", _l_shape),
        AllocationStrategy(
            "random_endpoint", "random", False, "non-convex", _random_endpoint, True
        ),
        AllocationStrategy(
            "random_switch", "random", True, "non-convex", _random_switch, True
        ),
    ]
}


def get_strategy(name: str) -> AllocationStrategy:
    try:
        return ALLOCATIONS[name]
    except KeyError:
        raise KeyError(
            f"unknown allocation strategy {name!r}; available: {sorted(ALLOCATIONS)}"
        ) from None


# --------------------------------------------------------------------------
# Partition construction
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Partition:
    """A concrete set of endpoints allocated to one job."""

    strategy: str
    topo: HyperX
    job_id: int
    size: int  # endpoints
    endpoints: np.ndarray  # (size,) linear endpoint ids, rank order
    switches: np.ndarray  # sorted unique switch ids touched

    @property
    def rank_to_endpoint(self) -> np.ndarray:
        return self.endpoints

    def endpoint_to_rank(self) -> Dict[int, int]:
        return {int(e): r for r, e in enumerate(self.endpoints)}


def num_blocks(topo: HyperX) -> int:
    """Base blocks of n**2 endpoints on ``topo``: n**(q-1)."""
    if topo.q < 2:
        raise ValueError(f"allocation strategies need q >= 2, got {topo}")
    return topo.n ** (topo.q - 1)


def _place(strat: AllocationStrategy, topo: HyperX, job_id: int,
           blk: np.ndarray, r_in: np.ndarray, seed: int) -> Partition:
    """Rank ``i`` at rank ``r_in[i]`` of base block ``blk[i]``."""
    n = topo.n
    num_blocks(topo)  # refuses q < 2
    rng = np.random.default_rng(seed) if strat.needs_rng else None
    sw, c = strat(blk, r_in // n, r_in % n, n, topo.q, rng)
    return Partition(
        strategy=strat.name,
        topo=topo,
        job_id=job_id,
        size=len(r_in),
        endpoints=(sw * topo.concentration + c).astype(np.int64),
        switches=np.unique(sw).astype(np.int64),
    )


def allocate_partition(
    strategy: str | AllocationStrategy,
    topo: HyperX,
    job_id: int,
    size: int | None = None,
    seed: int = 0,
) -> Partition:
    """Allocate ``size`` endpoints (default n**2) for job ``job_id``.

    Jobs of k*n**2 endpoints take base blocks p = job_id*k .. job_id*k + k-1
    (consecutive blocks, paper Section 6.2).  Sizes that are not multiples of
    n**2 take a prefix of the final block.  The random permutations are keyed
    by ``seed`` only (machine-wide), so different jobs on one machine draw
    from the same permutation and stay disjoint.
    """
    strat = get_strategy(strategy) if isinstance(strategy, str) else strategy
    block = topo.n * topo.n
    if size is None:
        size = block
    if size <= 0 or size > topo.num_endpoints:
        raise ValueError(f"partition size {size} out of range")
    k = -(-size // block)  # blocks needed (ceil)
    ranks = np.arange(size, dtype=np.int64)
    return _place(strat, topo, job_id, job_id * k + ranks // block,
                  ranks % block, seed)


def allocate_blocks(
    strategy: str | AllocationStrategy,
    topo: HyperX,
    block_ids: Sequence[int] | np.ndarray,
    job_id: int = 0,
    size: int | None = None,
    seed: int = 0,
) -> Partition:
    """Allocate a partition over an *arbitrary* list of base blocks.

    Generalizes :func:`allocate_partition` (which always takes consecutive
    blocks) to the fragmented-machine case: the online scheduler hands the
    block slots it found free, in rank order.  Rank ``r`` lands in block
    ``block_ids[r // n**2]``; ``size`` (default: all of them) may take a
    prefix of the final block.  All strategies keep distinct block ids in
    ``[0, n**(q-1))`` pairwise disjoint, so any subset of slots yields a
    valid partition.
    """
    strat = get_strategy(strategy) if isinstance(strategy, str) else strategy
    block = topo.n * topo.n
    blocks = num_blocks(topo)
    block_ids = np.asarray(block_ids, dtype=np.int64)
    if block_ids.ndim != 1 or len(block_ids) == 0:
        raise ValueError(f"need a non-empty 1D block list, got {block_ids!r}")
    if len(np.unique(block_ids)) != len(block_ids):
        raise ValueError(f"duplicate block ids in {block_ids.tolist()}")
    if (block_ids < 0).any() or (block_ids >= blocks).any():
        raise ValueError(
            f"block ids {block_ids.tolist()} out of range [0, {blocks})")
    if size is None:
        size = len(block_ids) * block
    if not 0 < size <= len(block_ids) * block:
        raise ValueError(
            f"size {size} does not fit {len(block_ids)} blocks of {block}"
        )
    ranks = np.arange(size, dtype=np.int64)
    return _place(strat, topo, job_id, block_ids[ranks // block],
                  ranks % block, seed)


def scavenge_partition(
    free_mask: np.ndarray, topo: HyperX, job_id: int, size: int
) -> Partition:
    """The first ``size`` free endpoints as a structureless partition.

    Shared last-resort placement used by every allocator's ``scavenge``;
    the caller does its own record-keeping (free-mask update, job table).
    """
    free = np.flatnonzero(free_mask)
    if len(free) < size:
        raise RuntimeError(f"no {size} free endpoints to scavenge")
    eps = free[:size].astype(np.int64)
    return Partition(
        strategy="scavenge", topo=topo, job_id=job_id, size=size,
        endpoints=eps, switches=np.unique(eps // topo.concentration),
    )


def machine_partitions(
    strategy: str | AllocationStrategy,
    topo: HyperX,
    num_jobs: int,
    job_size: int | None = None,
    seed: int = 0,
) -> list[Partition]:
    """All ``num_jobs`` disjoint partitions on one machine instance."""
    return [
        allocate_partition(strategy, topo, j, job_size, seed) for j in range(num_jobs)
    ]


def endpoint_owner(partitions: list[Partition], num_endpoints: int) -> np.ndarray:
    """(num_endpoints,) array: partition index owning each endpoint, -1 if free.

    Raises if two partitions claim the same endpoint (allocation bug).
    """
    owner = np.full(num_endpoints, -1, dtype=np.int64)
    for i, part in enumerate(partitions):
        if (owner[part.endpoints] != -1).any():
            clash = part.endpoints[owner[part.endpoints] != -1]
            raise ValueError(
                f"partition overlap: job {i} ({part.strategy}) claims endpoints "
                f"{clash[:8].tolist()} already owned"
            )
        owner[part.endpoints] = i
    return owner


# --------------------------------------------------------------------------
# Incremental job allocator (SLURM-like resource manager facade)
# --------------------------------------------------------------------------
class JobAllocator:
    """Incremental resource manager over one HyperX machine.

    Tracks free endpoints; serves jobs by trying the requested strategy's
    next free base block(s).  This is the layer the training launcher and the
    elastic runtime talk to.
    """

    def __init__(self, topo: HyperX, strategy: str = "diagonal", seed: int = 0):
        self.topo = topo
        self.strategy = get_strategy(strategy)
        self.seed = seed
        self.free = np.ones(topo.num_endpoints, dtype=bool)
        self.failed = np.zeros(topo.num_endpoints, dtype=bool)
        self.jobs: Dict[int, Partition] = {}
        self._next_job = 0

    def capacity(self) -> int:
        return int(self.free.sum())

    def allocate(self, size: int | None = None, strategy: str | None = None) -> Partition:
        strat = get_strategy(strategy) if strategy else self.strategy
        block = self.topo.n * self.topo.n
        size = size or block
        k = -(-size // block)
        # n**(q-1) slots on a well-balanced machine
        max_jobs = self.topo.num_endpoints // (k * block)
        for slot in range(max_jobs):
            part = allocate_partition(strat, self.topo, slot, size, self.seed)
            if self.free[part.endpoints].all():
                part = dataclasses.replace(part, job_id=self._next_job)
                self.free[part.endpoints] = False
                self.jobs[self._next_job] = part
                self._next_job += 1
                return part
        raise RuntimeError(
            f"no free {strat.name} partition of size {size} "
            f"(free endpoints: {self.capacity()})"
        )

    def scavenge(self, size: int) -> Partition:
        """Last-resort placement: the first ``size`` free endpoints, with no
        allocation structure at all.  The elastic runtime falls back to this
        when every strategy (including the stochastic ones) fails on the
        fragmented fleet."""
        part = scavenge_partition(self.free, self.topo, self._next_job, size)
        self.free[part.endpoints] = False
        self.jobs[part.job_id] = part
        self._next_job += 1
        return part

    def release(self, job_id: int) -> None:
        part = self.jobs.pop(job_id)
        # failed endpoints stay out of the pool until repaired
        self.free[part.endpoints] = ~self.failed[part.endpoints]

    def fail_endpoints(self, endpoints: np.ndarray) -> list[int]:
        """Mark endpoints as failed (not free); return affected job ids."""
        endpoints = np.asarray(endpoints, dtype=np.int64)
        affected = []
        for jid, part in self.jobs.items():
            if np.intersect1d(part.endpoints, endpoints).size:
                affected.append(jid)
        self.failed[endpoints] = True
        self.free[endpoints] = False
        return affected

    def repair_endpoints(self, endpoints: np.ndarray) -> None:
        """Return repaired endpoints to the free pool (maintenance done)."""
        endpoints = np.asarray(endpoints, dtype=np.int64)
        self.failed[endpoints] = False
        owned = np.zeros_like(self.free)
        for part in self.jobs.values():
            owned[part.endpoints] = True
        self.free[endpoints] = ~owned[endpoints]
