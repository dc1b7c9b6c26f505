"""The reference's own lowering of an interference lane on a q-D HyperX.

Restated from the q-D forms of the paper's allocation functions (DESIGN.md
sec. 4), independently of the program.  A base block is n**2 endpoints,
rank r = n*r_y + r_x inside it; block p written in base n is the digits
p_{q-2} .. p_0 (p_0 least significant); switch coordinates are listed
most significant first, and an endpoint is ``switch * conc + offset``.

  row           (p_{q-2}, .., p_0, r_y); r_x
  diagonal      (r_y, r_y + p_{q-2}, .., r_y + p_0) mod n; r_x
  full_spread   (p_{q-2}, .., p_1, r_y, r_x); p_0
  rectangular   boxes of side 2 in every dimension but the last and
                n / 2**(q-1) in the last; box p in mixed radix over the
                tile counts, last dimension least significant; inside a
                box r_y walks the first dimension fastest; r_x
  l_shape       the 2D L of p_0 in the last two dimensions, at leading
                coordinates (p_{q-2}, .., p_1); r_x
  random_endpoint  pi = permutation(n**(q+1)) of the (switch, offset < n)
                slots, slot pi[(p*n**2 + r) mod n**(q+1)]
  random_switch sigma = permutation(n**q), switch sigma[(p*n + r_y) mod
                n**q]; r_x

At q = 2 each is the paper's formula (``reference/traffic.placement``).
The lane is the one ``reference/traffic.interference_lane`` builds, on
the machine's n**q * conc endpoints.
"""

from __future__ import annotations

import numpy as np

from reference.hyperx_sim import Lane
from reference.traffic import derangement


def placement(strategy: str, n: int, q: int, conc: int, p: int, size: int,
              seed: int = 0) -> np.ndarray:
    """Endpoint of each rank of a ``size``-rank job from base block ``p``."""
    r = np.arange(size)
    blk = p + r // (n * n)
    r_y, r_x = (r % (n * n)) // n, r % n
    digit = [blk // n ** (q - 2 - i) % n for i in range(q - 1)]
    sw = None
    if strategy == "row":
        coords, c = digit + [r_y], r_x
    elif strategy == "diagonal":
        coords, c = [r_y] + [(r_y + d) % n for d in digit], r_x
    elif strategy == "full_spread":
        coords, c = digit[:-1] + [r_y, r_x], digit[-1]
    elif strategy == "rectangular":
        if n % 2 ** (q - 1):
            raise ValueError(f"no rectangular tiling of n={n} in q={q}")
        sides = [2] * (q - 1) + [n // 2 ** (q - 1)]
        tiles = [n // s for s in sides]
        coords = []
        for d in range(q):
            box = blk // int(np.prod(tiles[d + 1:])) % tiles[d]
            inner = r_y // int(np.prod(sides[:d])) % sides[d]
            coords.append(box * sides[d] + inner)
        c = r_x
    elif strategy == "l_shape":
        p0, half = digit[-1], n // 2
        vert = r_y < half
        coords = digit[:-1] + [np.where(vert, (p0 + r_y) % n, p0),
                               np.where(vert, p0, (p0 + r_y - half + 1) % n)]
        c = r_x
    elif strategy == "random_endpoint":
        pi = np.random.default_rng(seed).permutation(n ** (q + 1))
        slot = pi[(blk * n * n + r_y * n + r_x) % n ** (q + 1)]
        sw, c = slot // n, slot % n
    elif strategy == "random_switch":
        sigma = np.random.default_rng(seed).permutation(n ** q)
        sw, c = sigma[(blk * n + r_y) % n ** q], r_x
    else:
        raise ValueError(f"no reference placement for {strategy!r}")
    if sw is None:
        sw = sum(x * n ** (q - 1 - d) for d, x in enumerate(coords))
    return sw * conc + c


def interference_lane(strategy: str, n: int, q: int, conc: int,
                      kernel: str, ranks: int, partition: int,
                      background: dict | None, warmup: int) -> Lane:
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
    E = n ** q * conc
    k = ranks
    eps = placement(strategy, n, q, conc, partition, k)
    T = k - 1
    free = np.setdiff1d(np.arange(E), eps) if background is not None \
        else np.zeros(0, dtype=np.int64)
    b = len(free)
    R = k + b
    dst = np.full((R, T, 1), -1, dtype=np.int64)
    npkts = np.zeros((R, T, 1), dtype=np.int64)
    deg = np.zeros((R, T), dtype=np.int64)
    recv = np.zeros((R, T), dtype=np.int64)
    dst[:k, :, 0] = (np.arange(k)[:, None] + np.arange(T)[None, :] + 1) % k
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
