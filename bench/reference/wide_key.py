"""The packed arbitration key with a head field wider than 17 bits.

Each cycle every queue head gets a key: a random draw in the high bits,
the head's index in the low ``hb`` bits, so keys are unique and the
smallest key wins an output.  ``hb`` is 17 for a machine under 2**17 heads
a lane and, above that, the least width that holds every head index
(``head_bits``); at least ``MIN_RANDOM_BITS`` random bits must remain.

``hyperx_sim.Draws`` gives the 15-bit draw ``d = bits >> 17`` (``bits``
the cycle's 32 random bits of a head) and ``simulate`` packs
``d << 17 | head``.  With the low ``hb - 17`` bits of ``d`` cleared,

    (d & ~(2**(hb-17) - 1)) << 17  =  ((bits >> hb) << (hb - 17)) << 17
                                   =  (bits >> hb) << hb,

so ``simulate`` then packs ``(bits >> hb) << hb | head``, the wide key,
and nothing else of the reference changes.  At ``hb = 17`` nothing is
cleared and the draws are ``hyperx_sim.Draws``' own.
"""

from __future__ import annotations

import numpy as np

from reference.hyperx_sim import Draws as Draws17

MIN_HEAD_BITS = 17
MIN_RANDOM_BITS = 8


def head_bits(heads: int) -> int:
    """Width of the head field for ``heads`` queue heads a lane; refuses a
    machine that would leave fewer than ``MIN_RANDOM_BITS`` random bits."""
    hb = max(MIN_HEAD_BITS, heads.bit_length())
    if 32 - hb < MIN_RANDOM_BITS:
        raise ValueError(f"{heads} queue heads per lane leave {32 - hb} "
                         f"random bits in the packed arbitration key, fewer "
                         f"than {MIN_RANDOM_BITS}")
    return hb


class Draws:
    """``hyperx_sim.Draws`` for a head field of ``hb >= 17`` bits: the same
    jitter, and the arbitration draw with its low ``hb - 17`` bits
    cleared."""

    def __init__(self, H: int, QN: int, hb: int, block: int = 32):
        if hb < 17:
            raise ValueError(f"a {hb}-bit head field leaves more random bits "
                             f"than the reference's 15-bit draw holds")
        self._draws = Draws17(H, QN, block)
        self._keep = np.uint32(~((1 << (hb - 17)) - 1) & 0x7FFF)

    def __call__(self, seed: int, t: int):
        jitter, arb = self._draws(seed, t)
        return jitter, arb & self._keep
