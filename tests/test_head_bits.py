"""The packed arbitration key's head field: its width is a function of the
heads a lane holds, 17 bits below 2**17 (the programs and random streams
of a fixed 17-bit field) and the least width that holds the heads above,
refused below 8 random bits.  A wide field is checked against the plain
numpy reference (``bench/reference``) with the wide-key draws."""

import os
import sys

import jax
import numpy as np
import pytest

from repro.core.engine import SimEngine, tables
from repro.core.engine.step import packed_keys
from repro.core.engine.tables import build_static_tables, head_field_bits
from repro.core.hyperx import HyperX

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:  # the plain reference lives with the benchmark
    sys.path.insert(0, BENCH)

from generator import FIELDS, fields_of  # noqa: E402
from reference import hyperx_sim, wide_key  # noqa: E402
from reference.traffic import interference_lane  # noqa: E402

# heads a lane: 4x4 omniwar, the paper's 8x8, the 3D radix-32 machine, the
# largest 17-bit one, 2**17, the 22x22 radix-64 machine, 8^3 under val
WIDTHS = [(960, 17), (7_680, 17), (114_688, 17), ((1 << 17) - 1, 17),
          (1 << 17, 18), (159_720, 18), (212_992, 18), ((1 << 18) - 1, 18),
          (1 << 18, 19), ((1 << 24) - 1, 24)]


@pytest.mark.parametrize("H, bits", WIDTHS)
def test_head_field_width(H, bits):
    assert head_field_bits(H) == bits
    assert wide_key.head_bits(H) == bits  # the reference restates the rule


@pytest.mark.parametrize("H", [1 << 24, (1 << 25) + 3, 1 << 31])
def test_head_field_refused_below_the_random_floor(H):
    with pytest.raises(ValueError, match="fewer than 8"):
        head_field_bits(H)
    with pytest.raises(ValueError, match="fewer than 8"):
        wide_key.head_bits(H)


@pytest.mark.parametrize("H", [960, 7_680, 114_688, (1 << 17) - 1])
def test_packed_keys_are_the_17_bit_keys_below_2_17(H):
    """Below 2**17 heads the key is ``(bits >> 17) << 17 | head`` bit for
    bit, so those machines keep their arbitration outcomes."""
    for seed in (0, 5):
        k = jax.random.fold_in(jax.random.PRNGKey(seed), 3)
        bits = np.asarray(jax.random.bits(k, (H,), dtype=jax.numpy.uint32))
        old = ((bits >> 17) << 17) | np.arange(H, dtype=np.uint32)
        got = np.asarray(packed_keys(k, H, head_field_bits(H)))
        np.testing.assert_array_equal(got, old)


def test_packed_keys_wide_field_is_the_reference_key():
    """At 22x22 (18 bits) the key is what the reference packs from the
    wide-key draw: the 15-bit draw with its low bit cleared, << 17."""
    H = 159_720
    k = jax.random.fold_in(jax.random.PRNGKey(7), 11)
    bits = np.asarray(jax.random.bits(k, (H,), dtype=jax.numpy.uint32))
    draw = (bits >> 17) & np.uint32(0x7FFE)
    want = (draw << np.uint32(17)) | np.arange(H, dtype=np.uint32)
    got = np.asarray(packed_keys(k, H, 18))
    np.testing.assert_array_equal(got, want)
    assert len(np.unique(got)) == H


def test_radix64_machine_tables():
    """The paper's radix-64 2D HyperX, 22x22 with 22 endpoints a switch:
    159,720 heads a lane under omniwar, an 18-bit head field, 44 network
    port slots a head, and switch and endpoint ids in int16 tables."""
    st = build_static_tables(HyperX(n=22, q=2, concentration=22),
                             mode="omniwar")
    assert (st.S, st.E, st.IN, st.V) == (484, 10_648, 66, 5)
    assert st.H == 484 * 66 * 5 == 159_720
    assert st.head_bits == 18 and st.q * st.n == 44
    assert st.h_sw.dtype == st.ep_sw.dtype == np.int16
    assert st.inj_base.dtype == np.int32


@pytest.fixture
def wide_field(monkeypatch):
    """A 19-bit head field on a small machine: the 17-bit minimum raised
    in the engine and in the reference, so the wide path runs at n = 4."""
    monkeypatch.setattr(tables, "MIN_HEAD_BITS", 19)
    monkeypatch.setattr(wide_key, "MIN_HEAD_BITS", 19)
    build_static_tables.cache_clear()  # no 17-bit tables from other tests
    yield 19
    build_static_tables.cache_clear()  # and none of these left for them


def _small_lane():
    from repro.traffic import (AppSpec, BackgroundSpec, PhaseSpec,
                               ScenarioSpec, build_workload)

    topo = HyperX(n=4, q=2, concentration=4)
    bg = {"pattern": "random_permutation", "packets": 1, "seed": 99}
    wl = build_workload(topo, ScenarioSpec(
        apps=(AppSpec(phases=PhaseSpec("all_to_all"), placement="diagonal",
                      ranks=16),),
        background=BackgroundSpec(**bg), fabric_partitioning="shared",
        warmup=40))
    lane = interference_lane("diagonal", 4, 4, "all_to_all", 16, 0, bg, 40)
    return topo, wl, lane


def test_wide_key_engine_matches_reference(wide_field):
    topo, wl, lane = _small_lane()
    engine = SimEngine(topo, mode="omniwar", num_pools=wl.num_pools)
    assert engine.head_bits == wide_field
    mc = hyperx_sim.Machine(n=4, q=2, conc=4)
    hb = wide_key.head_bits(mc.NQ)
    assert hb == wide_field
    draws = wide_key.Draws(mc.NQ, mc.QN, hb)
    narrow = hyperx_sim.Draws(mc.NQ, mc.QN)
    differs = 0
    for seed in (1, 2):
        got = fields_of(engine.run(wl, seed=seed, horizon=20_000))
        assert got["completed"]
        want = hyperx_sim.simulate(mc, lane, seed, 20_000, draws=draws)
        assert {k: got[k] for k in FIELDS} == want
        old = hyperx_sim.simulate(mc, lane, seed, 20_000, draws=narrow)
        differs += sum(got[k] != old[k] for k in FIELDS)
    assert differs >= 1  # the 17-bit keys give other outcomes


@pytest.mark.parametrize("option", [{"arb": "pallas"}, {"kernel": "pallas"}],
                         ids=["pallas_arbiter", "fused_kernel"])
def test_wide_key_reaches_the_pallas_paths(wide_field, option):
    """Both Pallas paths take the packed keys ready-made, so with a wide
    head field they stay bit-exact against the lax path."""
    topo, wl, _ = _small_lane()
    lax = SimEngine(topo, mode="omniwar", num_pools=wl.num_pools)
    pal = SimEngine(topo, mode="omniwar", num_pools=wl.num_pools, **option)
    assert pal.head_bits == lax.head_bits == wide_field
    assert pal.run(wl, seed=3, horizon=20_000) == \
        lax.run(wl, seed=3, horizon=20_000)
