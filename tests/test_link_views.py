"""Per-link views and port picks of the cycle kernel's route and
arbitration block.

``LinkViews`` reads what lies behind every queue head's network ports as
one row per switch or per link, broadcast over the switch's heads, in
place of ``(H, q*n)`` gathers; ``step.pick`` reads a row's value at the
head's chosen port with a one-hot reduce, in place of a per-head gather,
and ``step.first_min`` finds that port as ``jnp.argmin`` would;
``step.ring_front`` reads every queue's front packet from the slot-major
queue fields with a select over the ring slots, in place of a per-queue
gather.  Pinned here: each view, pick and front read equals the gather it
replaced,
computed from the tables with the original index formulas, for every head
(empty heads with stale hop counts, rows with no legal port and rows with
tied minima included), packed and unpacked tables; and the default
engine's step lowers with no gather of ``H * q*n`` results and none out of
an ``(H, q*n)`` or ``(H, OUT)`` row, of the output tokens or of a queue
field, and with no H-wide read of a per-rank or per-endpoint table (a
packet's accounting rows ride in its queue fields from injection), so the
per-head gathers cannot silently return.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import traffic as tr
from repro.core.allocation import allocate_partition
from repro.core.engine import SimEngine, build_step, init_state
from repro.core.engine.step import (
    LinkViews, SimState, first_min, pick, ring_front,
)
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


def _take(x, idx):
    return np.take_along_axis(np.asarray(x), np.asarray(idx)[:, None], 1)[:, 0]


@pytest.mark.parametrize("pack_tables", [True, False],
                         ids=["packed", "int32"])
@pytest.mark.parametrize("name,topo,mode,pools,rate", CASES,
                         ids=[c[0] for c in CASES])
def test_port_picks_equal_the_gathers_they_replace(name, topo, mode, pools,
                                                   rate, pack_tables):
    st = build_static_tables(topo, mode=mode, num_pools=pools,
                             pack_tables=pack_tables)
    S, OUT, H, QN = st.S, st.OUT, st.H, st.q * st.n
    rng = np.random.default_rng(16)
    big = 1 << 28
    # costs from a narrow range, so most rows tie at their minimum; some
    # ports and a quarter of the rows have no legal port (BIGCOST)
    cost = rng.integers(0, 4, (H, QN)).astype(np.int32)
    cost[rng.random((H, QN)) < 0.3] = big
    cost[rng.random(H) < 0.25] = big
    cost2 = np.where(rng.random((H, QN)) < 0.5, big, cost).astype(np.int32)
    is_min = rng.random((H, QN)) < 0.4
    escalate = rng.random((H, QN)) < 0.2
    vcn = rng.integers(0, st.V, H).astype(np.int32)
    won2 = rng.random(H) < 0.5

    best = jnp.argmin(cost, axis=1).astype(jnp.int32)
    best2 = jnp.argmin(cost2, axis=1).astype(jnp.int32)
    best2c = jnp.minimum(best2, QN - 1)
    chosen = jnp.minimum(jnp.where(won2, best2, best), QN - 1)
    for c, b in ((cost, best), (cost2, best2)):
        lo, first = first_min(jnp.asarray(c))
        np.testing.assert_array_equal(first, b)
        np.testing.assert_array_equal(lo < big, _take(c, b) < big)
        np.testing.assert_array_equal(pick(jnp.asarray(c), b), _take(c, b))
    qi_down = LinkViews(st).down_index(vcn)
    for idx in (best, best2c, chosen):
        for x in (qi_down, jnp.asarray(is_min), jnp.asarray(escalate)):
            got = pick(x, idx)
            assert got.dtype == x.dtype
            np.testing.assert_array_equal(got, _take(x, idx))

    # reads at the head's own switch's outputs: any port, and the
    # ejection columns by the packet's offset
    links = LinkViews(st)
    h_sw = np.asarray(st.h_sw, np.int64)
    tokens = rng.integers(-1, 3, S * OUT).astype(np.int32)
    rows = jnp.asarray(tokens.reshape(S, OUT))
    out_port = rng.integers(0, OUT, H).astype(np.int32)
    dof = rng.integers(0, st.conc, H).astype(np.int32)
    np.testing.assert_array_equal(pick(links.per_switch(rows), out_port),
                                  tokens[h_sw * OUT + out_port])
    np.testing.assert_array_equal(
        pick(links.per_switch(rows[:, QN:]), dof),
        tokens[h_sw * OUT + QN + dof])
    # the edge rows were drawn
    assert (cost == big).all(axis=1).any()
    lo = cost.min(axis=1, keepdims=True)
    assert ((cost == lo).sum(axis=1) > 1)[lo[:, 0] < big].any()


@pytest.mark.parametrize("dtype", ["int32", "bool"])
@pytest.mark.parametrize("cap", [1, 2, 8])
def test_ring_front_equals_the_gather_it_replaces(cap, dtype):
    nq = 384
    rng = np.random.default_rng(18)
    f = (rng.integers(-5, 1 << 20, cap * nq) if dtype == "int32"
         else rng.random(cap * nq) < 0.5).astype(dtype)
    qhead = rng.integers(0, cap, nq).astype(np.int32)
    got = ring_front(jnp.asarray(f), jnp.asarray(qhead))
    assert got.dtype == f.dtype and got.shape == (nq,)
    np.testing.assert_array_equal(
        got, f.reshape(cap, nq)[qhead, np.arange(nq)])
    # every slot was drawn as a head
    assert len(set(qhead.tolist())) == cap


def _gathers(hlo: str) -> list:
    """``(operand dims, operand element type, result dims)`` of every
    gather."""
    out = []
    for line in hlo.splitlines():
        if "stablehlo.gather" in line:
            dims, ty, res = re.findall(
                r": \(tensor<([0-9x]+)x(\w+)>, .*-> tensor<([0-9x]+)x\w+>",
                line)[-1]
            out.append((tuple(int(d) for d in dims.split("x")), ty,
                        tuple(int(d) for d in res.split("x"))))
    return out


def test_default_step_picks_ports_without_per_head_gathers():
    topo = HyperX(n=8, q=2)
    part = allocate_partition("row", topo, 0)
    wl = tr.compose_workload(topo, [(tr.all_to_all(16), part)])
    engine = SimEngine(topo, mode="omniwar", num_pools=wl.num_pools)
    st = engine.static
    wt = engine.prepare(wl).tables
    hlo = jax.jit(build_step(st)).lower(init_state(st, wt, 0), wt).as_text()
    gathers = _gathers(hlo)
    assert gathers, "no gather found: the guard reads nothing"
    # no pick out of a head's port row or output row ...
    rows = {(st.H, st.q * st.n), (st.H, st.OUT)}
    assert not [g for g in gathers if g[0] in rows]
    # ... nor a per-head read of the int32 output tokens at a chosen
    # output (the arbiter's own read of its uint32 grant table stays)
    outs = [g[1] for g in gathers if g[0] == (st.S * st.OUT,)
            and g[2] == (st.H,)]
    assert outs == ["ui32", "ui32"]


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


@pytest.mark.parametrize("mode", ["omniwar", "val"])
def test_default_step_reads_queue_fronts_without_gathers(mode):
    topo = HyperX(n=8, q=2)
    part = allocate_partition("row", topo, 0)
    wl = tr.compose_workload(topo, [(tr.all_to_all(16), part)])
    engine = SimEngine(topo, mode=mode, num_pools=wl.num_pools)
    st = engine.static
    wt = engine.prepare(wl).tables
    state = init_state(st, wt, 0)
    assert state.f_dst.shape == (st.NQ * st.CAP,)
    assert state.f_imd.shape == ((st.NQ * st.CAP,) if mode == "val" else (1,))
    hlo = jax.jit(build_step(st)).lower(state, wt).as_text()
    gathers = _gathers(hlo)
    assert gathers, "no gather found: the guard reads nothing"
    assert not [g for g in gathers
                if int(np.prod(g[0])) == st.NQ * st.CAP]


@pytest.mark.parametrize("mode", ["omniwar", "val"])
def test_default_step_delivers_without_per_head_table_gathers(mode):
    # a packet's sent and got rows ride in f_rank and f_step from its
    # injection; a seventh queue field would add an H-wide scatter-set to
    # every move, which costs more than the lookups it could save
    assert [f for f in SimState._fields if f.startswith("f_")] == [
        "f_dst", "f_der", "f_hop", "f_rank", "f_step", "f_birth", "f_imd"]
    topo = HyperX(n=8, q=2)
    part = allocate_partition("row", topo, 0)
    wl = tr.compose_workload(topo, [(tr.all_to_all(16), part)])
    engine = SimEngine(topo, mode=mode, num_pools=wl.num_pools)
    st = engine.static
    wt = engine.prepare(wl).tables
    hlo = jax.jit(build_step(st)).lower(init_state(st, wt, 0), wt).as_text()
    tables = {(wt.R,), (st.E,)}
    reads = [g for g in _gathers(hlo) if g[0] in tables]
    # injection reads the per-rank and per-endpoint tables once an endpoint
    assert [g for g in reads if g[2] == (st.E,)], "the guard reads nothing"
    assert not [g for g in reads if g[2] == (st.H,)]
