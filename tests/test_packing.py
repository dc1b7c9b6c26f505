"""Packed-table tests: dtype selection, overflow guards, and the property
that int8/int16 packing is invisible in every SimResult field.

The property test runs under hypothesis when the host has it and falls
back to a fixed seeded sample of the same space otherwise (the container
image may not ship hypothesis; the property must still be exercised).
"""

import numpy as np
import pytest

from repro import traffic as tr
from repro.core.allocation import allocate_partition
from repro.core.engine import SimEngine, pack, pack_dtype
from repro.core.engine.tables import build_static_tables
from repro.core.hyperx import HyperX

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - image-dependent
    HAVE_HYPOTHESIS = False


# ------------------------------------------------------------ dtype selection
def test_pack_dtype_boundaries():
    assert pack_dtype(0) == np.int8
    assert pack_dtype(127) == np.int8
    assert pack_dtype(128) == np.int16
    assert pack_dtype(32767) == np.int16
    assert pack_dtype(32768) == np.int32


def test_pack_dtype_rejects_negative_bound():
    with pytest.raises(ValueError):
        pack_dtype(-1)


def test_pack_casts_and_keeps_sentinels():
    a = pack(np.array([-1, 0, 100]), 100)
    assert a.dtype == np.int8
    assert a.tolist() == [-1, 0, 100]
    assert pack(np.array([1000]), 1000).dtype == np.int16


def test_pack_overflow_guard():
    """Values beyond the declared bound must be refused, not wrapped."""
    with pytest.raises(OverflowError):
        pack(np.array([128]), 127)
    with pytest.raises(OverflowError):
        pack(np.array([-129]), 127)  # past the -bound-1 sentinel headroom


# ----------------------------------------------------- largest-k overflow path
def test_largest_k_machines_widen_to_int32():
    """The overflow guard at scale: bounds past int16 must select int32.

    A HyperX with S > 32767 switches (n=200, q=2 -> 40000) exceeds every
    packed dtype for switch-id tables; pack_dtype must fall back to int32
    rather than wrap.  (Bound-derived, so no table needs to be built.)
    """
    big = HyperX(n=200, q=2)
    assert big.num_switches == 40_000
    assert pack_dtype(big.num_switches - 1) == np.int32
    a = pack(np.array([big.num_switches - 1]), big.num_switches - 1)
    assert a.dtype == np.int32 and int(a[0]) == 39_999


def test_static_tables_pack_by_topology_bounds():
    """Mid-size machine: switch ids need int16, coordinates fit int8."""
    topo = HyperX(n=16, q=2)  # S = 256, n = 16
    st_tables = build_static_tables(topo, mode="omniwar", num_pools=1,
                                    max_deroutes=None, cap=8,
                                    penalty_packets=4, pack_tables=True)
    assert np.asarray(st_tables.nbr).dtype == np.int16    # bound S-1 = 255
    assert np.asarray(st_tables.coords).dtype == np.int8  # bound n-1 = 15
    unpacked = build_static_tables(topo, mode="omniwar", num_pools=1,
                                   max_deroutes=None, cap=8,
                                   penalty_packets=4, pack_tables=False)
    assert np.array_equal(np.asarray(st_tables.nbr, dtype=np.int32),
                          np.asarray(unpacked.nbr, dtype=np.int32))


# ------------------------------------------------------------- the property
def _packed_matches_reference(n, q, strategy, kind, seed):
    """Packed and int32-reference engines must agree on every field."""
    topo = HyperX(n=n, q=q)
    k = min(8, topo.num_endpoints)
    part = allocate_partition(strategy, topo, 0, size=k)
    app = tr.all_to_all(k) if kind == "a2a" else tr.uniform(k, packets=3)
    wl = tr.compose_workload(topo, [(app, part)])
    packed = SimEngine(topo, mode="omniwar", pack=True).run(
        wl, seed=seed, horizon=4000)
    ref = SimEngine(topo, mode="omniwar", pack=False).run(
        wl, seed=seed, horizon=4000)
    assert packed == ref  # dataclass equality: every field bit-identical


if HAVE_HYPOTHESIS:

    @settings(max_examples=10, deadline=None)
    @given(
        n=st.sampled_from([3, 4]),
        q=st.just(2),  # the allocator's supported envelope (paper machines)
        strategy=st.sampled_from(["row", "diagonal", "full_spread"]),
        kind=st.sampled_from(["a2a", "uniform"]),
        seed=st.integers(min_value=0, max_value=3),
    )
    def test_packed_tables_bit_identical_property(n, q, strategy, kind, seed):
        _packed_matches_reference(n, q, strategy, kind, seed)

else:

    @pytest.mark.parametrize(
        "n,q,strategy,kind,seed",
        [
            (3, 2, "row", "a2a", 0),
            (3, 2, "diagonal", "uniform", 1),
            (4, 2, "full_spread", "a2a", 2),
            (4, 2, "row", "uniform", 3),
            (4, 2, "diagonal", "a2a", 0),
            (3, 2, "full_spread", "uniform", 2),
        ],
    )
    def test_packed_tables_bit_identical_property(n, q, strategy, kind, seed):
        _packed_matches_reference(n, q, strategy, kind, seed)
