"""Fused route+arbitrate Pallas megakernel (``SimEngine(kernel="pallas")``).

The cycle kernel's dominant block — candidate masks, the occupancy +
deroute-penalty + jitter cost, the argmin port choice, and *both*
arbitration rounds including the round-2 token/room re-route — is
switch-local end to end, so it lowers to **one** ``pallas_call`` with one
program instance per switch.  Three structural facts make that possible:

  * a queue head can only request outputs of its own switch, and heads
    are switch-major in queue order (the same layout ``arb.py`` exploits);
  * the downstream queue behind port ``p`` of switch ``s`` lives at
    ``(nbr[s,p], in_port_at_nb[s,p])`` — and that (neighbour, in-port)
    pair identifies the *link from s* uniquely, so every round-1 arrival
    the round-2 room check ``qlen + arr1 < CAP`` needs was produced by
    switch ``s`` itself.  ``arr1`` never has to leave VMEM: it is a
    per-switch histogram over (port, pool, VC) signatures;
  * packed arbitration keys (``random bits << head_bits | global head
    index``, built by the step kernel: ``tables.head_field_bits``) are
    globally unique, so masked integer min is tie-free and the kernel is
    **bit-exact** against the lax reference on every backend.

Per cycle the lax path materializes ~a dozen ``(H, q*n)`` intermediates
in HBM (``unaligned``/``is_min``/``legal``/``cost``/``qi_down``/``room``/
``cost2``/...).  Here they are all VMEM-resident per program: the only
``(H, q*n)`` array that still travels through HBM is the jitter draw,
which must be generated by the host PRNG stream to stay bit-identical.
Cross-switch reads are gathered per switch in XLA before the call — the
routing destination's coordinates per head, the neighbour in-port
occupancy behind each port, and the ``(P*V, q*n)`` slab of queue
lengths behind them — because Mosaic has no 1-D gathers; inside the
kernel a head picks its entry with a one-hot compare.  Everything is
blocked per switch, with unit dims that keep each block's two minor
dims equal to the array's (Mosaic's tiling rule).

Off TPU the kernel runs in Pallas interpret mode (CPU CI); on TPU
``interpret=None`` resolves to a compiled kernel.  Parity across all
four routing policies, faults, and telemetry is regression-pinned in
``tests/test_route_kernel.py``.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.engine.arb import INVALID_KEY, order_keys
from repro.core.engine.tables import onward_index
from repro.route import get_policy

I32 = jnp.int32


def make_fused_router(st, interpret: bool | None = None) -> Callable:
    """Build the fused route+arbitrate primitive for one configuration.

    Returns ``fused(exists, at_dst, dof, der, vcn, route_dsw, link_ok_t,
    n_dead_t, qlen, port_occ, busy_dec, jitter, packed)`` producing
    ``(won, won2, qi_best, best_min, esc_chosen, arr1_tgt, g1, g2)`` —
    exactly the signals the rest of the cycle kernel consumes, with
    values bit-identical to the lax reference block in ``step.py``.
    """
    S, OUT, H, IN = st.S, st.OUT, st.H, st.IN
    QN = st.q * st.n
    P, V, CAP = st.P, st.V, st.CAP
    PV = P * V
    PEN, m, q = st.PEN, st.m, st.q
    adaptive = get_policy(st.mode).adaptive_deroutes
    if H % S:
        raise ValueError(f"H={H} not switch-major divisible by S={S}")
    HS = H // S
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # a numpy scalar (not a jax array): pallas kernels may only close over
    # literals — jax-array constants must travel as inputs
    BIGCOST = np.int32(1 << 28)

    # static per-switch rows, widened once at build time (the tables may
    # be int8/int16 packed); all are trace constants shared by every cycle
    coords_np = np.asarray(st.coords, dtype=np.int32)          # (S, q)
    pdim_np = np.asarray(st.port_dim, dtype=np.int32)          # (QN,)
    down_np = (np.asarray(st.nbr, dtype=np.int32) * IN
               + np.asarray(st.in_port_at_nb, dtype=np.int32))  # (S, QN)
    down_t = jnp.asarray(down_np)
    cur_d_t = jnp.asarray(coords_np[:, pdim_np].reshape(S, 1, QN))
    pdim_t = jnp.asarray(pdim_np.reshape(1, QN))
    pval_t = jnp.asarray(st.port_val, dtype=I32).reshape(1, QN)
    hpool_t = st.h_pool.astype(I32).reshape(S, HS, 1)
    # minimal-only escapes read the links onward from each port's
    # neighbour: one (n, QN) slab per switch (tables.onward_index)
    onward_t = None if adaptive else jnp.asarray(
        onward_index(st).transpose(1, 0, 2))         # (S, n, QN)

    def kernel(exists_ref, at_dst_ref, dof_ref, der_ref, vcn_ref, pool_ref,
               key_ref, cdst_ref, jit_ref, curd_ref, down_ref, lok_ref,
               occ_ref, busy_ref, slab_ref, pdim_ref, pval_ref, ndead_ref,
               *refs):
        # the onward-link slab is an input of minimal-only policies only
        onward_ref = None if adaptive else refs[0]
        (won_ref, won2_ref, qib_ref, bmin_ref, esc_ref, a1t_ref,
         g1_ref, g2_ref) = refs[-8:]
        # heads run down the sublanes, ports along the lanes: per-head
        # values are (HS, 1) columns, per-port values (1, QN) rows, and
        # every gather is a one-hot select over a lane or sublane axis
        exists = exists_ref[0] != 0                  # (HS, 1)
        at_dst = at_dst_ref[0] != 0
        dof = dof_ref[0]
        der = der_ref[0]
        kv = pool_ref[0] * V + vcn_ref[0]            # (pool, VC) of next hop
        key = key_ref[0]                             # ordered int32, unique
        cdst = cdst_ref[0]                           # (HS, q) dest coords
        jitter = jit_ref[0]                          # (HS, QN)
        cur_d = curd_ref[0]                          # (1, QN)
        down = down_ref[0]                           # (1, QN) (nbr, in-port)
        healthy = lok_ref[0] != 0                    # (1, QN)
        occ_row = occ_ref[0]                         # (1, QN)
        busy = busy_ref[0]                           # (1, OUT)
        slab = slab_ref[0]                           # (PV, QN) qlen behind
        pdim = pdim_ref[...]                         # (1, QN)
        pval = pval_ref[...]

        pcol = jax.lax.broadcasted_iota(I32, (HS, QN), 1)
        oid = jax.lax.broadcasted_iota(I32, (HS, OUT), 1)

        def pick(onehot, x):
            """Per-head value of ``x`` at the one-hot lane: (HS, 1)."""
            return jnp.where(onehot, x, 0).sum(axis=1, keepdims=True)

        def per_kv(rows):
            """(HS, QN) value ``rows[kv[h]][p]`` from PV rows of (1, QN)."""
            out = jnp.zeros((HS, QN), I32)
            for k in range(PV):
                out = jnp.where(kv == k, rows[k], out)
            return out

        def first_min(c):
            """Min cost and its first lane (``jnp.argmin`` tie rule)."""
            lo = c.min(axis=1, keepdims=True)
            return lo, jnp.where(c == lo, pcol, QN).min(axis=1, keepdims=True)

        def arbitrate(req):
            """Masked tie-free min per output: (won (HS, 1), count (1, OUT))."""
            grant = jnp.where(req, key, INVALID_KEY).min(axis=0,
                                                         keepdims=True)
            wonrow = req & (key == grant)
            return (wonrow.any(axis=1, keepdims=True),
                    wonrow.astype(I32).sum(axis=0, keepdims=True))

        # ---- candidate network ports (all (HS, QN), VMEM-resident) ----
        dst_d = jnp.zeros((HS, QN), I32)
        for d in range(q):
            dst_d = jnp.where(pdim == d, cdst[:, d:d + 1], dst_d)
        unaligned = cur_d != dst_d
        not_self = pval != cur_d
        is_min = (pval == dst_d) & unaligned
        qi_down = down * PV + kv
        qlen_down = per_kv([slab[k:k + 1, :] for k in range(PV)])
        room = qlen_down < CAP
        avail_net = busy[:, :QN] < 2
        if adaptive:
            reserve = jnp.minimum(ndead_ref[...], max(m - 1, 0))
            base = unaligned & not_self & healthy
            escalate = (
                ~(is_min & healthy).any(axis=1, keepdims=True)
                & base & (der > 0)
            )
            legal = (
                (base & (is_min | (der > reserve)) | escalate)
                & room & avail_net
            )
        else:
            is_min_h = is_min & healthy
            escape = (
                ~is_min_h.any(axis=1, keepdims=True)
                & unaligned & not_self & healthy & (der > 0)
            )
            onward_rows = onward_ref[0] != 0         # (n, QN)
            onward = escape & (dst_d == 0) & onward_rows[0:1, :]
            for w in range(1, st.n):
                onward = onward | (escape & (dst_d == w)
                                   & onward_rows[w:w + 1, :])
            has_onward = onward.any(axis=1, keepdims=True)
            escalate = (has_onward & onward) | (~has_onward & escape)
            legal = (is_min_h | escalate) & room & avail_net
        cost = occ_row * 8 + jnp.where(is_min, 0, PEN) + jitter
        cost = jnp.where(legal, cost, BIGCOST)
        best_cost, best = first_min(cost)
        has_port = best_cost < BIGCOST
        at_best = pcol == best
        best_min1 = (at_best & is_min).any(axis=1, keepdims=True)
        out_port = jnp.where(at_dst, QN + dof, best)
        requesting = exists & (at_dst | has_port)
        requesting = requesting & (pick(oid == out_port, busy) < 2)

        # ---- round 1: masked tie-free min per output ----
        won1, g1 = arbitrate(requesting & (oid == out_port))
        qi_best1 = pick(at_best, qi_down)

        # ---- round-1 arrivals, switch-locally ----
        # the queue behind (port p, pool, vc) is fed only by this switch,
        # so arr1 is a histogram of net winners over (port, pool, VC)
        # signatures — equal to the global scatter's values at every
        # queue this switch's heads can observe
        w1net = at_best & (won1 & ~at_dst)
        arr1 = per_kv([(w1net & (kv == k)).astype(I32).sum(axis=0,
                                                           keepdims=True)
                       for k in range(PV)])

        # ---- round 2: losers re-route to ports with tokens + room ----
        tokens = (2 - busy) - g1                     # (1, OUT)
        loser = requesting & ~won1
        tok_net = tokens[:, :QN] > 0
        room2 = qlen_down + arr1 < CAP
        cost2 = jnp.where(legal & tok_net & room2, cost, BIGCOST)
        best2_cost, best2 = first_min(cost2)
        has2 = best2_cost < BIGCOST
        ej_ok = at_dst & (pick(oid == QN + dof, tokens) > 0)
        out2 = jnp.where(at_dst, QN + dof, best2)
        # selects between booleans are spelled as logic: Mosaic cannot
        # lower a bool-valued select
        req2v = loser & (ej_ok | (~at_dst & has2))
        won2, g2 = arbitrate(req2v & (oid == out2))
        won = won1 | won2

        # ---- final winner signals ----
        at_b2 = pcol == jnp.minimum(best2, QN - 1)
        qi_best = jnp.where(won2, pick(at_b2, qi_down), qi_best1)
        bmin = ((won2 & (at_b2 & is_min).any(axis=1, keepdims=True))
                | (~won2 & best_min1))
        at_chosen = pcol == jnp.minimum(jnp.where(won2, best2, best), QN - 1)
        esc = (at_chosen & escalate).any(axis=1, keepdims=True)

        won_ref[0] = won.astype(I32)
        won2_ref[0] = won2.astype(I32)
        qib_ref[0] = qi_best
        bmin_ref[0] = bmin.astype(I32)
        esc_ref[0] = esc.astype(I32)
        a1t_ref[0] = pick(at_chosen, arr1)
        g1_ref[0] = g1
        g2_ref[0] = g2

    # one program per switch; the unit dims keep every block's two minor
    # dims equal to the array's, as Mosaic's (8, 128) tiling requires
    def per_switch(*tail):
        return pl.BlockSpec((1,) + tail, lambda s: (s,) + (0,) * len(tail))

    def whole(*shape):
        return pl.BlockSpec(shape, lambda s: (0,) * len(shape))

    head = per_switch(HS, 1)
    row = per_switch(1, QN)
    call = pl.pallas_call(
        kernel,
        grid=(S,),
        in_specs=[
            head, head, head, head, head, head, head,  # per-head columns
            per_switch(HS, q), per_switch(HS, QN),     # dest coords, jitter
            row, row, row, row,                        # cur_d/down/lok/occ
            per_switch(1, OUT), per_switch(PV, QN),    # busy, qlen slab
            whole(1, QN), whole(1, QN), whole(1, 1),   # pdim/pval/ndead
        ] + ([] if adaptive else [per_switch(st.n, QN)]),  # onward links
        out_specs=[head] * 6 + [per_switch(1, OUT)] * 2,
        out_shape=[jax.ShapeDtypeStruct((S, HS, 1), I32)] * 6
        + [jax.ShapeDtypeStruct((S, 1, OUT), I32)] * 2,
        interpret=interpret,
        name="route_arbitrate",
    )

    def fused(exists, at_dst, dof, der, vcn, route_dsw, link_ok_t, n_dead_t,
              qlen, port_occ, busy_dec, jitter, packed):
        def col(x):
            return x.astype(I32).reshape(S, HS, 1)

        # switch-level gathers hoisted out of the kernel: destination
        # coordinates per head, congestion and queue lengths behind each
        # of this switch's ports
        cdst = st.coords[route_dsw].astype(I32).reshape(S, HS, q)
        occ = port_occ[down_t].reshape(S, 1, QN)
        slab = qlen.reshape(S * IN, PV)[down_t].transpose(0, 2, 1)
        lok = link_ok_t.astype(I32)
        onward = [] if adaptive else [lok.reshape(-1)[onward_t]]
        outs = call(
            col(exists), col(at_dst), col(dof), col(der), col(vcn), hpool_t,
            order_keys(packed).reshape(S, HS, 1), cdst,
            jitter.reshape(S, HS, QN), cur_d_t, down_t.reshape(S, 1, QN),
            lok.reshape(S, 1, QN), occ,
            busy_dec.reshape(S, 1, OUT), slab, pdim_t, pval_t,
            jnp.reshape(n_dead_t, (1, 1)).astype(I32), *onward,
        )
        won, won2, qib, bmin, esc, a1t, g1, g2 = outs
        return (
            won.reshape(H).astype(bool), won2.reshape(H).astype(bool),
            qib.reshape(H), bmin.reshape(H).astype(bool),
            esc.reshape(H).astype(bool), a1t.reshape(H),
            g1.reshape(S * OUT), g2.reshape(S * OUT),
        )

    return fused
