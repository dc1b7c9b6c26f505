"""Per-link views of the cycle kernel's route and arbitration block.

``LinkViews`` reads what lies behind every queue head's network ports as
one row per switch or per link, broadcast over the switch's heads, in
place of ``(H, q*n)`` gathers.  Pinned here: each view equals the gather
it replaced, computed from the tables with the original index formulas,
for every head (empty heads with stale hop counts included), packed and
unpacked tables; and the default engine's step lowers with no gather of
``H * q*n`` results, so the per-head gathers cannot silently return.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import traffic as tr
from repro.core.allocation import allocate_partition
from repro.core.engine import SimEngine, build_step, init_state
from repro.core.engine.step import LinkViews
from repro.core.engine.tables import build_static_tables
from repro.core.hyperx import HyperX
from repro.route import random_link_faults

# (name, topology, mode, pools, fault rate)
CASES = [
    ("omniwar_8x8", HyperX(n=8, q=2), "omniwar", 1, 0.0),
    ("val_p2", HyperX(n=4, q=2, concentration=4), "val", 2, 0.0),
    ("omniwar_q3", HyperX(n=4, q=3, concentration=2), "omniwar", 1, 0.0),
    ("omniwar_faults", HyperX(n=4, q=2), "omniwar", 1, 0.2),
]


@pytest.mark.parametrize("pack_tables", [True, False],
                         ids=["packed", "int32"])
@pytest.mark.parametrize("name,topo,mode,pools,rate", CASES,
                         ids=[c[0] for c in CASES])
def test_views_equal_the_gathers_they_replace(name, topo, mode, pools, rate,
                                              pack_tables):
    st = build_static_tables(topo, mode=mode, num_pools=pools,
                             pack_tables=pack_tables)
    S, IN, P, V, H, QN = st.S, st.IN, st.P, st.V, st.H, st.q * st.n
    rng = np.random.default_rng(14)
    # queue lengths with many empty queues; hop counts as stale as a
    # queue's last packet left them, beyond the VC budget too
    qlen = rng.integers(0, st.CAP + 1, st.NQ) * (rng.random(st.NQ) < 0.5)
    hop = rng.integers(0, 2 * V + 2, H)
    vcn = np.minimum(hop + 1, V - 1).astype(np.int32)
    arr1 = rng.integers(0, 3, st.NQ)
    link_ok = (np.ones((S, QN), bool) if rate == 0.0
               else random_link_faults(topo, rate, seed=3))

    h_sw = np.asarray(st.h_sw, np.int64)
    h_pool = np.asarray(st.h_pool, np.int64)
    nb = np.asarray(st.nbr, np.int64)[h_sw]
    ipnb = np.asarray(st.in_port_at_nb, np.int64)[h_sw]
    qi_down = ((nb * IN + ipnb) * P + h_pool[:, None]) * V + vcn[:, None]
    port_occ = qlen.reshape(S * IN, P * V).sum(axis=1)
    busy = rng.integers(0, 3, S * st.OUT)

    links = LinkViews(st)
    np.testing.assert_array_equal(links.down_index(vcn), qi_down)
    np.testing.assert_array_equal(
        links.down_view(jnp.asarray(qlen, jnp.int32), vcn), qlen[qi_down])
    np.testing.assert_array_equal(
        links.down_view(jnp.asarray(qlen + arr1, jnp.int32), vcn),
        qlen[qi_down] + arr1[qi_down])
    np.testing.assert_array_equal(
        links.per_link(jnp.asarray(port_occ, jnp.int32)),
        port_occ[nb * IN + ipnb])
    np.testing.assert_array_equal(links.per_switch(link_ok), link_ok[h_sw])
    np.testing.assert_array_equal(
        links.per_switch(busy.reshape(S, st.OUT)[:, :QN]),
        busy[h_sw[:, None] * st.OUT + np.arange(QN)[None, :]])
    assert rate == 0.0 or not link_ok.all()


def _gather_result_sizes(hlo: str) -> list:
    sizes = []
    for line in hlo.splitlines():
        if "stablehlo.gather" in line:
            shape = re.findall(r"-> tensor<([0-9x]+)x\w+>", line)[-1]
            sizes.append(int(np.prod([int(d) for d in shape.split("x")])))
    return sizes


def test_default_step_lowers_without_per_head_port_gathers():
    topo = HyperX(n=8, q=2)
    part = allocate_partition("row", topo, 0)
    wl = tr.compose_workload(topo, [(tr.all_to_all(16), part)])
    engine = SimEngine(topo, mode="omniwar", num_pools=wl.num_pools)
    st = engine.static
    wt = engine.prepare(wl).tables
    hlo = jax.jit(build_step(st)).lower(init_state(st, wt, 0), wt).as_text()
    sizes = _gather_result_sizes(hlo)
    assert sizes, "no gather found: the guard reads nothing"
    assert st.H * st.q * st.n not in sizes
