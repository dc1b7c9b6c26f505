"""Pallas arbitration kernel: bit-exactness pin against the lax reference.

The packed keys make ties impossible (low 17 bits are the unique global
head index), so a masked integer min per output is deterministic on every
backend — the kernel must match the scatter-min reference *bitwise*, both
at the round level (random request matrices) and end-to-end through the
engine.  On CPU CI the kernel runs in Pallas interpret mode; on TPU the
same `make_arbiter(..., interpret=None)` resolves to a compiled kernel.
"""

import numpy as np
import pytest

from repro import traffic as tr
from repro.core.allocation import allocate_partition
from repro.core.engine import SimEngine, make_arbiter
from repro.core.hyperx import HyperX

SMALL = HyperX(n=4, q=2)


def _random_round(rng, S, OUT, HS, invalid_frac=0.3):
    """Switch-local random requests + unique packed keys (engine layout)."""
    H = S * HS
    sw = np.arange(H) // HS
    port = rng.integers(0, OUT, size=H)
    req = (sw * OUT + port).astype(np.int32)
    off = rng.random(H) < invalid_frac
    req[off] = S * OUT + rng.integers(0, 5, size=off.sum())  # "not requesting"
    packed = ((rng.integers(0, 1 << 15, size=H).astype(np.uint32) << 17)
              | np.arange(H, dtype=np.uint32))
    return req, packed


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_pallas_round_matches_lax_reference(seed):
    S, OUT, HS = 5, 7, 12
    lax_arb = make_arbiter(S, OUT, S * HS, "lax")
    pallas_arb = make_arbiter(S, OUT, S * HS, "pallas", interpret=True)
    rng = np.random.default_rng(seed)
    for _ in range(5):
        req, packed = _random_round(rng, S, OUT, HS)
        won_l, g_l = lax_arb(req, packed)
        won_p, g_p = pallas_arb(req, packed)
        assert np.array_equal(np.asarray(won_l), np.asarray(won_p))
        assert np.array_equal(np.asarray(g_l), np.asarray(g_p))
        # sanity: exactly one winner per granted output, none elsewhere
        assert int(np.asarray(won_p).sum()) == int(np.asarray(g_p).sum())
        assert np.asarray(g_p).max(initial=0) <= 1


def test_pallas_round_all_idle_and_full_contention():
    S, OUT, HS = 3, 4, 6
    H = S * HS
    lax_arb = make_arbiter(S, OUT, H, "lax")
    pallas_arb = make_arbiter(S, OUT, H, "pallas", interpret=True)
    packed = ((np.full(H, 7, dtype=np.uint32) << 17)
              | np.arange(H, dtype=np.uint32))
    # nobody requests
    idle = np.full(H, S * OUT, dtype=np.int32)
    for a, b in zip(lax_arb(idle, packed), pallas_arb(idle, packed)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # every head of each switch fights for the same output 0
    clash = ((np.arange(H) // HS) * OUT).astype(np.int32)
    won_l, g_l = lax_arb(clash, packed)
    won_p, g_p = pallas_arb(clash, packed)
    assert np.array_equal(np.asarray(won_l), np.asarray(won_p))
    assert np.array_equal(np.asarray(g_l), np.asarray(g_p))
    assert int(np.asarray(won_p).sum()) == S  # one winner per switch


def test_make_arbiter_rejects_unknown_backend():
    with pytest.raises(ValueError):
        make_arbiter(2, 2, 4, "scatter")
    with pytest.raises(ValueError):
        make_arbiter(3, 2, 7, "pallas")  # H not switch-major divisible


# --------------------------------------------------------------- end-to-end
def _a2a_workload(strategy: str):
    part = allocate_partition(strategy, SMALL, 0)
    return tr.compose_workload(SMALL, [(tr.all_to_all(16), part)])


def test_engine_pallas_arb_bit_identical():
    """The regression pin: arb='pallas' must reproduce arb='lax' exactly —
    single runs, the batched grid, and a deroute-heavy policy ('val', which
    stresses the second arbitration round via intermediate hops)."""
    lax_eng = SimEngine(SMALL, mode="omniwar", arb="lax")
    pal_eng = SimEngine(SMALL, mode="omniwar", arb="pallas")
    wls = [_a2a_workload(s) for s in ("row", "diagonal", "full_spread")]
    for wl, seed in zip(wls, (0, 3, 9)):
        assert pal_eng.run(wl, seed=seed, horizon=5000) == lax_eng.run(
            wl, seed=seed, horizon=5000)
    assert pal_eng.run_grid(wls, seeds=(0, 7), horizon=5000) == \
        lax_eng.run_grid(wls, seeds=(0, 7), horizon=5000)

    wl = _a2a_workload("row")
    lax_val = SimEngine(SMALL, mode="val", num_pools=wl.num_pools)
    pal_val = SimEngine(SMALL, mode="val", num_pools=wl.num_pools,
                        arb="pallas")
    assert pal_val.run(wl, seed=1, horizon=5000) == lax_val.run(
        wl, seed=1, horizon=5000)


def test_packed_key_head_field_guard():
    """The packed key's head field holds every head of a lane: 17 bits
    under 2**17 heads, the least width that holds them above (fewer random
    bits), and the engine refuses a machine that would leave fewer than
    ``MIN_RANDOM_BITS`` random bits (H >= 2**24)."""
    from repro.core.engine.tables import (MIN_RANDOM_BITS,
                                          build_static_tables,
                                          head_field_bits)

    topo = HyperX(n=8, q=3)
    st = build_static_tables(topo, mode="omniwar")
    assert st.H == 512 * 32 * 7 < 1 << 17 and st.head_bits == 17
    for mode in ("val", "ugal"):  # 13 VCs: 212,992 heads
        st = build_static_tables(topo, mode=mode)
        assert st.H == 512 * 32 * 13 and st.head_bits == 18
    assert MIN_RANDOM_BITS == 8
    assert head_field_bits((1 << 24) - 1) == 24
    with pytest.raises(ValueError, match="fewer than 8"):
        head_field_bits(1 << 24)
    with pytest.raises(ValueError, match="fewer than 8"):
        SimEngine(topo, mode="omniwar", max_deroutes=1021)  # V = 1,025
