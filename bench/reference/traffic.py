"""The reference's own lowering of a scenario to rank step tables.

Restated from the paper (Sec. 4 allocation functions, Sec. 6.1 traffic)
and the scenario conventions, independently of the program: one target
job on base block ``partition`` under an allocation strategy, plus an
optional random-permutation background over every endpoint the job left
free.  Targets take ranks ``0..k-1``, the background the ranks after.
"""

from __future__ import annotations

import numpy as np

from reference.hyperx_sim import Lane


def placement(strategy: str, n: int, conc: int, p: int, size: int,
              seed: int = 0) -> np.ndarray:
    """Endpoint of each rank: f(p, r_y, r_x) = (s_y, s_x, c), 2D only."""
    r = np.arange(size)
    blk = p + r // (n * n)
    r_y, r_x = (r % (n * n)) // n, r % n
    if strategy == "row":
        s_y, s_x, c = blk % n, r_y, r_x
    elif strategy == "full_spread":
        s_y, s_x, c = r_y, r_x, blk % n
    elif strategy == "diagonal":
        s_y, s_x, c = r_y, (r_y + blk) % n, r_x
    elif strategy == "rectangular":
        # disjoint 2 x n/2 tiles (the printed formula's offsets swapped)
        s_y, s_x, c = (r_y % 2 + 2 * (blk // 2)) % n, \
            (r_y // 2 + (n // 2) * (blk % 2)) % n, r_x
    elif strategy == "l_shape":
        vert = r_y < n // 2
        s_y = np.where(vert, (blk + r_y) % n, blk % n)
        s_x = np.where(vert, blk % n, (blk + r_y - n // 2 + 1) % n)
        c = r_x
    elif strategy == "random_endpoint":
        pi = np.random.default_rng(seed).permutation(n ** 3)
        tgt = pi[(blk * n * n + r_y * n + r_x) % n ** 3]
        s_y, s_x, c = tgt // (n * n), (tgt // n) % n, tgt % n
    elif strategy == "random_switch":
        sigma = np.random.default_rng(seed).permutation(n * n)
        tgt = sigma[(blk * n + r_y) % (n * n)]
        s_y, s_x, c = tgt // n, tgt % n, r_x
    else:
        raise ValueError(f"no reference placement for {strategy!r}")
    return (s_y * n + s_x) * conc + c


def derangement(k: int, seed: int) -> np.ndarray:
    """A random permutation with each fixed point swapped with its
    successor, in index order."""
    perm = np.random.default_rng(seed).permutation(k)
    for i in np.flatnonzero(perm == np.arange(k)):
        j = (i + 1) % k
        perm[i], perm[j] = perm[j], perm[i]
    return perm


def interference_lane(strategy: str, n: int, conc: int, kernel: str,
                      ranks: int, partition: int, background: dict | None,
                      warmup: int) -> Lane:
    """An all-to-all target job (k - 1 asynchronous steps; step i sends one
    packet to rank (r + i + 1) mod k and needs one from (r - i - 1) mod k),
    starting at ``warmup``, plus, when ``background`` is given, one infinite
    source per free endpoint sending one packet at a time to a fixed random
    partner from the start.  Any other kernel or background is refused."""
    if kernel != "all_to_all":
        raise ValueError(f"the reference models only the all_to_all "
                         f"kernel, not {kernel!r}")
    if background is not None and (
            background.get("pattern") != "random_permutation"
            or background.get("packets") != 1
            or set(background) != {"pattern", "packets", "seed"}):
        raise ValueError(f"the reference models only a random_permutation "
                         f"background of 1 packet with a seed, not "
                         f"{background!r}")
    E = n * n * conc
    k = ranks
    eps = placement(strategy, n, conc, partition, k)
    T = k - 1
    free = np.setdiff1d(np.arange(E), eps) if background is not None \
        else np.zeros(0, dtype=np.int64)
    b = len(free)
    R = k + b
    dst = np.full((R, T, 1), -1, dtype=np.int64)
    npkts = np.zeros((R, T, 1), dtype=np.int64)
    deg = np.zeros((R, T), dtype=np.int64)
    recv = np.zeros((R, T), dtype=np.int64)
    r = np.arange(k)[:, None]
    dst[:k, :, 0] = (r + np.arange(T)[None, :] + 1) % k
    npkts[:k] = 1
    deg[:k] = 1
    recv[:k] = 1
    window = np.full(R, T, dtype=np.int64)
    start = np.full(R, warmup, dtype=np.int64)
    if b:
        dst[k:, 0, 0] = k + derangement(b, int(background["seed"]))
        npkts[k:, 0, 0] = 1
        deg[k:, 0] = 1
        window[k:] = 1
        start[k:] = 0
    return Lane(
        rank_ep=np.concatenate([eps, free]).astype(np.int64),
        infinite=np.arange(R) >= k,
        window=window, start=start,
        dst=dst, npkts=npkts, deg=deg, recv_need=recv,
    )
