"""The cycle kernel: one packet-time of the whole machine.

``build_step`` closes over the configuration's :class:`StaticTables` (trace
constants) and returns a pure function ``step(state, wt)`` operating on the
``(SimState, WorkloadTables)`` pair.  Because every workload-dependent array
arrives through ``wt`` — a pytree argument, not a closure constant — the
compiled step is shared by all workloads whose tables land in the same shape
bucket, and the surrounding while-loop can be ``jax.vmap``-ed over stacked
tables.

Routing is policy-driven: the ``mode`` string in the static tables resolves
through the :mod:`repro.route` registry, and the policy's static predicates
(candidate-set shape, Valiant intermediates, UGAL injection) specialize the
kernel at trace time.  Per-workload fault masks (``wt.link_ok``) exclude
dead links from every candidate set; minimal-only policies escalate to
budget-bounded deroutes when all minimal ports of a switch are dead, which
keeps worst-case hops inside the policy's declared hop-indexed VC budget
(deadlock freedom under faults).  With an all-healthy mask, ``min`` and
``omniwar`` are bit-identical to the seed simulator (regression-pinned).

The physics is unchanged from the seed simulator (see DESIGN.md §6 for the
CAMINOS fidelity deviations): packet-time granularity, input-queued FIFOs
with hop-indexed VCs per pool, table-driven routing with an occupancy +
deroute-penalty cost, two-round random separable allocation with a 2x
internal speedup token bucket, and the step/dependency engine that walks
the Workload step tables.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine.arb import make_arbiter
from repro.core.engine.route_kernel import make_fused_router
from repro.core.engine.tables import StaticTables, onward_index
from repro.core.engine.workload_tables import WorkloadTables
from repro.obs.probes import TelemetrySpec, TelemetryState
from repro.route import get_policy

I32 = jnp.int32
U32 = jnp.uint32


class SimState(NamedTuple):
    t: jnp.ndarray            # () int32 — current packet-time
    key: jnp.ndarray          # PRNG key
    # queue field arrays, flat (NQ * CAP,) and slot-major: slot c of queue
    # qi sits at c * NQ + qi, so row c (reshape(CAP, NQ)) is one dense vector
    f_dst: jnp.ndarray        # destination endpoint id
    f_der: jnp.ndarray        # deroutes left
    f_hop: jnp.ndarray        # hops taken
    f_rank: jnp.ndarray       # source rank
    f_step: jnp.ndarray       # source step index
    f_birth: jnp.ndarray      # injection time
    f_imd: jnp.ndarray        # Valiant intermediate switch (S = none);
                              # shape (1,) for policies without intermediates
    qhead: jnp.ndarray        # (NQ,) ring head
    qlen: jnp.ndarray         # (NQ,) occupancy
    busy: jnp.ndarray         # (S*OUT,) output-buffer tokens (2x speedup)
    # per-rank step engine
    cur_step: jnp.ndarray     # (R,)
    dst_i: jnp.ndarray        # (R,)
    pkt_i: jnp.ndarray        # (R,)
    completed: jnp.ndarray    # (R,) first incomplete step pointer
    sent: jnp.ndarray         # ((R+1)*T,) delivered sends per (rank, step)
    got: jnp.ndarray          # ((R+1)*T,) received packets per (rank, step)
    # metrics
    lat_sum: jnp.ndarray      # () float32 sum of target packet latencies
    n_delivered: jnp.ndarray  # () target packets delivered
    n_injected: jnp.ndarray   # () packets injected (all sources)
    hop_sum: jnp.ndarray      # () network hops of delivered target packets
    hop_max: jnp.ndarray      # () max hops over ALL ejected packets (VC bound)
    # resilience counters (fault epochs; all cheap extra accumulation)
    esc_count: jnp.ndarray    # () escalation-granted moves (re-escalated pkts)
    epoch_delivered: jnp.ndarray  # (NE,) target deliveries per fault epoch
    epoch_injected: jnp.ndarray   # (NE,) injections per fault epoch


def init_state(st: StaticTables, wt: WorkloadTables, seed) -> SimState:
    """Fresh simulation state for one workload (R/T taken from ``wt``)."""
    R, T = wt.R, wt.T
    use_imd = get_policy(st.mode).uses_intermediate

    def z(n):
        return jnp.zeros(n, dtype=I32)

    return SimState(
        t=jnp.int32(0), key=jax.random.PRNGKey(seed),
        f_dst=z(st.NQ * st.CAP), f_der=z(st.NQ * st.CAP),
        f_hop=z(st.NQ * st.CAP), f_rank=z(st.NQ * st.CAP),
        f_step=z(st.NQ * st.CAP), f_birth=z(st.NQ * st.CAP),
        f_imd=z(st.NQ * st.CAP) if use_imd else z(1),
        qhead=z(st.NQ), qlen=z(st.NQ), busy=z(st.S * st.OUT),
        cur_step=z(R), dst_i=z(R), pkt_i=z(R), completed=z(R),
        sent=z((R + 1) * T), got=z((R + 1) * T),
        lat_sum=jnp.float32(0.0),
        n_delivered=jnp.int32(0), n_injected=jnp.int32(0),
        hop_sum=jnp.int32(0), hop_max=jnp.int32(0),
        esc_count=jnp.int32(0),
        epoch_delivered=z(wt.NE), epoch_injected=z(wt.NE),
    )


def all_done(wt: WorkloadTables, state: SimState) -> jnp.ndarray:
    """All finite (target) ranks have completed their real steps."""
    return jnp.all(jnp.where(wt.finite, state.completed >= wt.n_steps, True))


class LinkViews:
    """Per-link views of what lies behind every head's network ports.

    Heads are switch-major (``H == S * HS``), so a value that depends only
    on a head's switch and port is one ``(S, q*n)`` row per switch,
    broadcast over its ``HS`` heads: :meth:`per_switch`.  Port ``p`` of
    switch ``s`` feeds one link, into in-port ``in_port_at_nb[s, p]`` of
    switch ``nbr[s, p]``; the ``S * q*n`` link rows are gathered once, with
    static indices, and broadcast.  Self ports read the switch's own
    in-port; they are never legal candidates.

    Everything here equals a gather through the head's downstream queue
    index ``qi_down[h, p] = ((nbr * IN + in_port_at_nb)[sw(h), p] * P +
    pool(h)) * V + vcn[h]`` (:meth:`down_index`), which the cycle kernel
    keeps only to enqueue into the chosen port's queue.
    """

    def __init__(self, st: StaticTables):
        self.S, self.IN, self.P, self.V = st.S, st.IN, st.P, st.V
        self.QN, self.HS = st.q * st.n, st.H // st.S
        self.link_row = (np.asarray(st.nbr, dtype=np.int32) * st.IN
                         + np.asarray(st.in_port_at_nb, dtype=np.int32)
                         ).reshape(-1)                    # (S * q*n,)
        self.h_pool = st.h_pool.astype(I32)
        self.onward_idx = onward_index(st)

    def onward(self, link_ok, dst_d):
        """Whether the neighbour behind each head's port (dimension d) has
        a healthy link of its own toward value ``dst_d[h, p]`` in d:
        ``(H, q*n)`` bool, one select per coordinate value."""
        rows = link_ok.reshape(-1)[self.onward_idx]     # (n, S, q*n)
        out = jnp.zeros(dst_d.shape, jnp.bool_)
        for w in range(rows.shape[0]):
            out = out | ((dst_d == w) & self.per_switch(rows[w]))
        return out

    def per_switch(self, a):
        """``(S, W) -> (H, W)``: every head sees its switch's row."""
        S, HS, W = self.S, self.HS, a.shape[1]
        return jnp.broadcast_to(a[:, None, :], (S, HS, W)).reshape(S * HS, W)

    def per_link(self, a):
        """A per-(switch, in-port) vector ``a`` (``(S*IN,)``) behind every
        head's ports: ``(H, q*n)``."""
        return self.per_switch(a[self.link_row].reshape(self.S, self.QN))

    def down_index(self, vcn):
        """``qi_down``: the downstream queue behind every head's ports."""
        base = (self.link_row * (self.P * self.V)).reshape(self.S, self.QN)
        return (self.per_switch(jnp.asarray(base))
                + (self.h_pool * self.V + vcn)[:, None])

    def down_view(self, x, vcn):
        """A per-queue vector ``x`` (``(NQ,)``) behind every head's ports
        at its next VC ``vcn``: exactly ``x[down_index(vcn)]``.  The link
        rows of ``(P, V)`` queues are gathered once; each head takes its
        pool's row and picks its VC with ``V - 1`` selects."""
        S, IN, P, V, QN = self.S, self.IN, self.P, self.V, self.QN
        rows = x.reshape(S * IN, P, V)[self.link_row]
        # (S, 1, P, 1, q*n, V): each link's queues, aligned with the heads'
        # (switch, in-port, pool, VC) layout and their ports
        rows = rows.reshape(S, QN, P, V).transpose(0, 2, 1, 3)[:, None, :, None]
        vc = vcn.reshape(S, IN, P, V, 1)
        out = jnp.broadcast_to(rows[..., 0], (S, IN, P, V, QN))
        for v in range(1, V):
            out = jnp.where(vc == v, rows[..., v], out)
        return out.reshape(S * self.HS, QN)


def pick(x, idx):
    """``x[h, idx[h]]`` for every row of an ``(H, W)`` array: a one-hot
    reduce over the row's ``W`` lanes, where a per-row gather would read
    one element a head (an ``idx`` outside ``[0, W)`` reads 0 or False)."""
    hot = jnp.arange(x.shape[1], dtype=I32)[None, :] == idx[:, None]
    if x.dtype == jnp.bool_:
        return (hot & x).any(axis=1)
    return jnp.where(hot, x, jnp.zeros((), x.dtype)).sum(axis=1)


def ring_front(f, qhead):
    """Every queue's front packet field: ``f.reshape(CAP, NQ)[qhead[i], i]``
    for a slot-major queue field ``f`` (``(CAP * NQ,)``) and ring heads
    ``qhead`` (``(NQ,)``).  ``CAP - 1`` selects over the dense slot rows,
    where a per-queue gather would read one element a queue."""
    rows = f.reshape(-1, qhead.shape[0])
    out = rows[0]
    for c in range(1, rows.shape[0]):
        out = jnp.where(qhead == c, rows[c], out)
    return out


def first_min(x):
    """Row minimum of an ``(H, W)`` array and the first lane holding it
    (``jnp.argmin``'s tie rule)."""
    lo = x.min(axis=1)
    lane = jnp.arange(x.shape[1], dtype=I32)[None, :]
    return lo, jnp.where(x == lo[:, None], lane, x.shape[1]).min(axis=1)


def packed_keys(k_arb, H: int, head_bits: int) -> jax.Array:
    """One cycle's arbitration keys: a random draw above each head's index
    in the low ``head_bits`` (``tables.head_field_bits``), unique, so the
    smallest key wins an output without ties."""
    arb_key = jax.random.bits(k_arb, (H,), dtype=U32) >> head_bits
    return (arb_key << head_bits) | jnp.arange(H, dtype=U32)


def build_step(
    st: StaticTables,
    telemetry: TelemetrySpec | None = None,
) -> Callable[[SimState, WorkloadTables], SimState]:
    """Return the cycle kernel for one static configuration.

    With ``telemetry=None`` (the default) the kernel is byte-for-byte the
    pre-telemetry step: ``step(state, wt) -> state``.  With a
    :class:`~repro.obs.probes.TelemetrySpec` the kernel operates on a
    ``(SimState, TelemetryState)`` carry and additionally accumulates the
    spec's windowed probes from the cycle's internal signals — grant
    counts per output port, queue-occupancy samples, deroute/escalation
    grants, and delivery latencies.  The probe updates are pure extra
    scatters appended after the physics; the simulated trajectory is
    bit-identical either way (pinned in ``tests/test_obs.py``).

    Every operation of a cycle runs under one ``jax.named_scope`` naming
    its stage, in order: ``heads``, ``route`` and ``arbitrate`` (or
    ``route_arbitrate``, the fused kernel), ``dequeue``, ``deliver``,
    ``move``, ``complete``, ``inject`` and ``telemetry``.  Scopes are
    metadata only, so the optimised program is the same without them;
    the profiler tags each device op with its scope path (``tf_op``),
    from which the benchmark reads device time per stage.
    """
    S, E, IN, OUT = st.S, st.E, st.IN, st.OUT
    P, V, NQ, H, CAP = st.P, st.V, st.NQ, st.H, st.CAP
    q, n, conc, m, PEN = st.q, st.n, st.conc, st.m, st.PEN
    policy = get_policy(st.mode)
    use_imd = policy.uses_intermediate
    coords, nbr, in_port_at_nb = st.coords, st.nbr, st.in_port_at_nb
    port_dim, port_val = st.port_dim, st.port_val
    h_pool, ep_sw = st.h_pool, st.ep_sw
    # tables may be packed to int8/int16 (bounds in tables.py); every value
    # that enters index arithmetic is widened to int32 exactly once — head
    # constants here (trace-time, folded), workload tables at their gather
    h_sw = st.h_sw.astype(I32)
    inj_base = st.inj_base.astype(I32)
    # per-round arbitration primitive: "lax" scatter-min or "pallas"
    # per-switch kernel (bit-exact — see repro.core.engine.arb)
    arbitrate = make_arbiter(st.S, st.OUT, st.H, st.arb)
    # fused route+arbitrate megakernel: kernel="pallas" replaces the whole
    # candidate/cost/argmin/two-round block with one per-switch pallas_call
    # (bit-exact — see repro.core.engine.route_kernel); the arb backend is
    # subsumed, since both rounds live inside the fused kernel
    fused_route = make_fused_router(st) if st.kernel == "pallas" else None
    links = LinkViews(st)
    # each switch's own coordinate in the dimension of each of its ports
    own_d = jnp.asarray(
        np.asarray(coords, dtype=np.int32)[:, np.asarray(port_dim)]
    )                                                   # (S, q*n)
    BIGCOST = jnp.int32(1 << 28)
    OOB = jnp.int32(NQ * CAP + 5)  # safely out of bounds => dropped scatters
    NOMID = jnp.int32(S)           # f_imd sentinel: no (remaining) intermediate
    spec = telemetry

    def step(carry, wt: WorkloadTables):
        if spec is None:
            state: SimState = carry
        else:
            state, tel = carry
        with jax.named_scope("heads"):
            R, T = wt.R, wt.T
            MAXD = wt.D
            t = state.t
            # fault epochs: select the mask (and its derived pool/reserve data)
            # active at cycle t.  NE is a *shape*, so this branch resolves at
            # trace time: the NE == 1 constant slice is the static-fault path,
            # bit-identical to the pre-epoch kernel (trace-counter-pinned);
            # NE > 1 pays exactly one gather on the epoch index per cycle.
            NE = wt.NE
            if NE == 1:
                ei = jnp.int32(0)
                link_ok_t = wt.link_ok[0]
                mid_pool_t = wt.mid_pool[0]
                n_mid_t = wt.n_mid[0]
                n_dead_t = wt.n_dead[0]
            else:
                ei = (jnp.sum(t >= wt.epoch_start.astype(I32)) - 1).astype(I32)
                link_ok_t = wt.link_ok[ei]
                mid_pool_t = wt.mid_pool[ei]
                n_mid_t = wt.n_mid[ei]
                n_dead_t = wt.n_dead[ei]
            key = jax.random.fold_in(state.key, t)
            # policies without intermediates split 3 keys exactly like the seed
            # engine, preserving bit-identical min/omniwar trajectories
            if use_imd:
                k_arb, k_jit, k_smp, k_mid = jax.random.split(key, 4)
            else:
                k_arb, k_jit, k_smp = jax.random.split(key, 3)

            qlen, qhead = state.qlen, state.qhead
            # per-(switch, in-port) total occupancy (packets over all pools+VCs):
            # the adaptive-routing congestion signal (CAMINOS counts phits in the
            # whole input buffer; penalty/range ratio ~1/8 is preserved).
            port_occ = qlen.reshape(S * IN, P * V).sum(axis=1)

            # the packet at the front of every queue
            exists = qlen > 0                                   # (H,)
            dst = ring_front(state.f_dst, qhead)
            der = ring_front(state.f_der, qhead)
            hop = ring_front(state.f_hop, qhead)
            dsw = dst // conc
            dof = dst % conc

            cur = h_sw
            at_dst = cur == dsw

            # Valiant phase 1 routes toward the packet's intermediate switch;
            # reaching it (or the final destination early) flips to phase 2.
            if use_imd:
                imd = ring_front(state.f_imd, qhead)
                in_phase1 = (imd < S) & (imd != cur) & ~at_dst
                route_dsw = jnp.where(in_phase1, imd, dsw)
            else:
                route_dsw = dsw

            # shared pre-kernel signals: the RNG draws must come off the host
            # key stream identically on both kernel paths (bit-exactness)
            busy_dec = jnp.maximum(state.busy - 1, 0)           # link served 1 pkt
            vcn = jnp.minimum(hop + 1, V - 1)                   # (H,) next VC
            jitter = jax.random.randint(k_jit, (H, q * n), 0, 8, dtype=I32)
            packed = packed_keys(k_arb, H, st.head_bits)

        def route_arbitrate_lax():
            with jax.named_scope("route"):
                # routing: candidate network ports (lax path)
                cdst = coords[route_dsw]                        # (H, q)
                pv = port_val[None, :]                          # (1, q*n)
                cur_d = links.per_switch(own_d)                 # (H, q*n)
                dst_d = jnp.repeat(cdst, n, axis=1)             # port d*n + v: dim d
                unaligned = cur_d != dst_d                      # (H, q*n)
                not_self = pv != cur_d
                is_min = (pv == dst_d) & unaligned
                healthy = links.per_switch(link_ok_t)           # (H, q*n) faults
                room = links.down_view(qlen, vcn) < CAP         # own queue has space
                occ = links.per_link(port_occ)                  # congestion signal
                avail_net = links.per_switch(
                    busy_dec.reshape(S, OUT)[:, :q * n]
                ) < 2
                if policy.adaptive_deroutes:
                    # Omni-WAR: deroutes in any unaligned dimension while budget
                    # lasts; dead links drop out of the candidate set.  Under
                    # faults, voluntary deroutes must keep a *reserve* (one unit
                    # per dead cable) so the budget can't be spent before a
                    # forced escape is needed — a packet stranded at a dead
                    # minimal link with der == 0 would wait forever.  The cap at
                    # m - 1 keeps one voluntary deroute alive at any fault count
                    # (a full-budget reserve would silently collapse omniwar
                    # into min-with-escalation machine-wide); the escalation
                    # term covers forced escapes below the reserve, exactly
                    # like the minimal-only policies.
                    reserve = jnp.minimum(n_dead_t, max(m - 1, 0))
                    base = unaligned & not_self & healthy
                    escalate = (
                        ~(is_min & healthy).any(axis=1, keepdims=True)
                        & base & (der[:, None] > 0)
                    )
                    legal = (
                        (base & (is_min | (der[:, None] > reserve)) | escalate)
                        & room & avail_net
                    )
                else:
                    # minimal-only (min / val / ugal): when every minimal port of
                    # this switch is dead, escalate to budget-bounded deroutes so
                    # packets can round the fault (hops stay inside the VC budget).
                    # An escape goes to a neighbour whose own link onward in the
                    # port's dimension is healthy, when there is one: else two
                    # switches that both lost their link to the target bounce a
                    # packet between them until its budget is spent.
                    is_min_h = is_min & healthy
                    escape = (
                        ~is_min_h.any(axis=1, keepdims=True)
                        & unaligned & not_self & healthy & (der[:, None] > 0)
                    )
                    onward = escape & links.onward(link_ok_t, dst_d)
                    escalate = jnp.where(
                        onward.any(axis=1, keepdims=True), onward, escape)
                    legal = (is_min_h | escalate) & room & avail_net
                cost = occ * 8 + PEN * (~is_min) + jitter
                cost = jnp.where(legal, cost, BIGCOST)
                cost_lo, best = first_min(cost)                 # (H,)
                has_port = cost_lo < BIGCOST
                best_min = pick(is_min, best)

                # a legal network port has output tokens (avail_net), so
                # only an ejection reads its output's tokens here
                out_port = jnp.where(at_dst, q * n + dof, best)
                busy_ej = links.per_switch(
                    busy_dec.reshape(S, OUT)[:, q * n:])        # (H, conc)
                requesting = exists & jnp.where(
                    at_dst, pick(busy_ej, dof) < 2, has_port)
                # NOTE: scatter/gather OOB markers must be POSITIVE out-of-range —
                # negative indices wrap NumPy-style in jnp .at[] even with
                # mode='drop'.
                OOB_OUT = jnp.int32(S * OUT + 1)
                req_out = jnp.where(requesting, cur * OUT + out_port, OOB_OUT)

            with jax.named_scope("arbitrate"):
                # iterative random arbitration (2x internal speedup)
                # Round 1: every head requests its best port; one random winner
                # per output.  Round 2 (separable-allocator iteration + the
                # paper's 2x crossbar speedup): losers re-route to their best
                # port that still has output tokens, enabling a second grant per
                # cycle per output.  The `busy` token bucket keeps sustained
                # link rate at 1 pkt/time.  Each round runs through the
                # configured arbiter backend (lax scatter-min or the per-switch
                # Pallas kernel — bit-exact).
                won1, g1 = arbitrate(req_out, packed)

                qi_down = links.down_index(vcn)                 # (H, q*n)
                qi_best1 = pick(qi_down, best)
                arr1 = jnp.zeros(NQ, dtype=I32).at[
                    jnp.where(won1 & ~at_dst, qi_best1, NQ + 1)
                ].add(1, mode="drop")
                tokens = (2 - busy_dec) - g1                    # remaining slots

                loser = requesting & ~won1
                # re-route: best legal port with tokens left and downstream room
                # (accounting for the round-1 arrival into the same queue)
                tok_sw = tokens.reshape(S, OUT)
                tok_net = links.per_switch(tok_sw[:, :q * n]) > 0
                room_2 = links.down_view(qlen + arr1, vcn) < CAP
                cost2 = jnp.where(legal & tok_net & room_2, cost, BIGCOST)
                cost2_lo, best2 = first_min(cost2)
                has2 = cost2_lo < BIGCOST
                tok_ej = links.per_switch(tok_sw[:, q * n:])    # (H, conc)
                ej_ok = at_dst & (pick(tok_ej, dof) > 0)
                out2 = jnp.where(at_dst, q * n + dof, best2)
                req2 = loser & jnp.where(at_dst, ej_ok, has2)
                req_out2 = jnp.where(req2, cur * OUT + out2, OOB_OUT)
                won2, g2 = arbitrate(req_out2, packed)
                won = won1 | won2

                # final chosen queue / minimality per winner
                best2c = jnp.minimum(best2, q * n - 1)
                qi_best = jnp.where(won2, pick(qi_down, best2c), qi_best1)
                bmin = jnp.where(won2, pick(is_min, best2c), best_min)
                # per-winner escalation flag + round-1 arrival count into the
                # winner's queue (the only arr1 value downstream code needs)
                chosen = jnp.minimum(jnp.where(won2, best2, best), q * n - 1)
                esc_chosen = pick(escalate, chosen)
                arr1_tgt = arr1[qi_best]
            return won, won2, qi_best, bmin, esc_chosen, arr1_tgt, g1, g2

        if fused_route is not None:
            with jax.named_scope("route_arbitrate"):
                # fused route+arbitrate megakernel (one pallas_call,
                # gridded per switch; candidate masks, cost, argmin and both
                # arbitration rounds stay VMEM-resident — bit-exact)
                (won, won2, qi_best, best_min, esc_chosen, arr1_tgt, g1, g2) = (
                    fused_route(
                        exists, at_dst, dof, der, vcn, route_dsw, link_ok_t,
                        n_dead_t, qlen, port_occ, busy_dec, jitter, packed,
                    )
            )
        else:
            (won, won2, qi_best, best_min, esc_chosen, arr1_tgt, g1, g2) = (
                route_arbitrate_lax()
            )

        with jax.named_scope("dequeue"):
            # output token update: +1 per grant (burst absorbed by 2x speedup)
            busy = busy_dec + g1 + g2

            # dequeue winners
            qhead = jnp.where(won, (qhead + 1) % CAP, qhead)
            dlen = jnp.zeros(NQ, dtype=I32).at[jnp.arange(H)].add(-won.astype(I32))

        with jax.named_scope("deliver"):
            # deliveries (ejection winners)
            eject = won & at_dst
            # the front packets, read at the ring heads before this dequeue
            rank = ring_front(state.f_rank, state.qhead)
            pstep = ring_front(state.f_step, state.qhead)
            birth = ring_front(state.f_birth, state.qhead)
            src_finite = wt.finite[rank]
            # sender-side accounting row (infinite sources -> trash row R)
            send_row = jnp.where(src_finite, rank, R)
            OOB_RT = jnp.int32((R + 1) * T + 1)
            sent = state.sent.at[
                jnp.where(eject, send_row * T + pstep, OOB_RT)
            ].add(1, mode="drop")
            drank = wt.ep_rank[dst].astype(I32)
            drank_ok = (drank >= 0) & wt.finite[jnp.maximum(drank, 0)]
            recv_row = jnp.where(drank_ok, drank, R)
            got = state.got.at[
                jnp.where(eject, recv_row * T + pstep, OOB_RT)
            ].add(1, mode="drop")
            tgt_del = eject & src_finite
            lat_pkt = (t - birth).astype(jnp.float32)
            lat_add = jnp.sum(jnp.where(tgt_del, lat_pkt, 0.0))
            lat_sum = state.lat_sum + lat_add
            hop_sum = state.hop_sum + jnp.sum(jnp.where(tgt_del, hop, 0))
            n_delivered = state.n_delivered + jnp.sum(tgt_del)
            # every ejection bounds the VC invariant, background included
            hop_max = jnp.maximum(
                state.hop_max, jnp.max(jnp.where(eject, hop, 0))
            )

        with jax.named_scope("move"):
            # network moves (enqueue downstream)
            net = won & ~at_dst
            # re-escalation accounting: moves granted through the forced
            # fault-escape candidate set (the port the winner took was only
            # legal because every minimal port was dead / reserve was spent)
            esc_count = state.esc_count + jnp.sum(net & esc_chosen)
            tgt_qi = qi_best
            # ring tail = head_pre + len_pre, invariant under same-cycle dequeue;
            # a round-2 arrival lands one slot behind the round-1 arrival.
            tgt_slot = (
                state.qhead[tgt_qi] + qlen[tgt_qi]
                + jnp.where(won2, arr1_tgt, 0)
            ) % CAP
            tgt_flat = jnp.where(net, tgt_slot * NQ + tgt_qi, OOB)
            f_dst = state.f_dst.at[tgt_flat].set(dst, mode="drop")
            f_der = state.f_der.at[tgt_flat].set(der - (~best_min), mode="drop")
            f_hop = state.f_hop.at[tgt_flat].set(hop + 1, mode="drop")
            f_rank = state.f_rank.at[tgt_flat].set(rank, mode="drop")
            f_step = state.f_step.at[tgt_flat].set(pstep, mode="drop")
            f_birth = state.f_birth.at[tgt_flat].set(birth, mode="drop")
            if use_imd:
                # a packet leaving its intermediate switch enters phase 2
                f_imd = state.f_imd.at[tgt_flat].set(
                    jnp.where(imd == cur, NOMID, imd), mode="drop"
                )
            else:
                f_imd = state.f_imd
            dlen = dlen.at[jnp.where(net, tgt_qi, NQ + 1)].add(1, mode="drop")

        with jax.named_scope("complete"):
            # step-engine: completion pointers
            # a rank is done after its *real* n_steps (padded steps never walked)
            completed = state.completed
            for _ in range(4):
                pidx = jnp.arange(R, dtype=I32) * T + jnp.minimum(completed, T - 1)
                comp = (completed >= wt.n_steps) | (
                    (sent[pidx] >= wt.total_sends[pidx])
                    & (got[pidx] >= wt.recv_need[pidx])
                )
                completed = completed + (
                    wt.finite & (completed < wt.n_steps) & comp
                )

            # skip empty (padded) steps
            cs = state.cur_step
            cs_deg = wt.deg[jnp.arange(R), jnp.minimum(cs, T - 1)]
            cs = cs + (wt.finite & (cs < wt.n_steps) & (cs_deg == 0))

        with jax.named_scope("inject"):
            r_of_e = wt.ep_rank.astype(I32)                     # (E,)
            r_safe = jnp.maximum(r_of_e, 0)
            e_fin = wt.finite[r_safe]
            e_cs = jnp.where(e_fin, cs[r_safe], 0)
            e_di = jnp.where(e_fin, state.dst_i[r_safe], 0)
            e_pk = jnp.where(e_fin, state.pkt_i[r_safe], 0)
            flat_td = jnp.minimum(e_cs, T - 1) * MAXD + e_di
            e_deg = wt.deg[r_safe, jnp.minimum(e_cs, T - 1)]
            e_np = wt.npkts[r_safe, flat_td]
            e_ns = wt.n_steps[r_safe]
            in_window = e_cs < jnp.minimum(e_ns, completed[r_safe] + wt.window[r_safe])
            has_work = jnp.where(
                e_fin, (e_cs < e_ns) & (e_di < e_deg) & in_window, True
            )
            has_work = has_work & (t >= wt.start_t[r_safe])
            inj_qi = inj_base + wt.pool[r_safe].astype(I32) * V
            has_room = qlen[inj_qi] + dlen[inj_qi] < CAP  # dlen: arrivals this cycle
            do_inj = (r_of_e >= 0) & has_work & has_room

            d_fixed = wt.sends_dst[r_safe, flat_td].astype(I32)
            rspan = jnp.maximum(wt.smp_hi[r_safe, flat_td] - wt.smp_lo[r_safe, flat_td], 1)
            rnd = jax.random.bits(k_smp, (E,), dtype=U32)
            d_smp = wt.smp_lo[r_safe, flat_td] + (rnd % rspan.astype(U32)).astype(I32)
            d_rank = jnp.where(wt.sampled[r_safe, flat_td], d_smp, d_fixed)
            d_rank = jnp.clip(d_rank, 0, R - 1)
            d_ep = wt.rank_ep[d_rank].astype(I32)

            inj_flat = jnp.where(
                do_inj, (state.qhead[inj_qi] + qlen[inj_qi]) % CAP * NQ + inj_qi,
                OOB,
            )
            f_dst = f_dst.at[inj_flat].set(d_ep, mode="drop")
            f_der = f_der.at[inj_flat].set(jnp.int32(m), mode="drop")
            f_hop = f_hop.at[inj_flat].set(0, mode="drop")
            f_rank = f_rank.at[inj_flat].set(r_safe, mode="drop")
            f_step = f_step.at[inj_flat].set(jnp.where(e_fin, e_cs, 0), mode="drop")
            f_birth = f_birth.at[inj_flat].set(t, mode="drop")
            if use_imd:
                # Valiant intermediate: one uniform draw per packet from the
                # healthy pool carried in the workload tables (mid_pool/n_mid
                # are device data — seeds and fault grids vmap, no retracing)
                rmid = jax.random.bits(k_mid, (E,), dtype=U32)
                span = jnp.maximum(n_mid_t, 1).astype(U32)
                mid = mid_pool_t[(rmid % span).astype(I32)].astype(I32)
                if policy.adaptive_injection:
                    # UGAL-L: best minimal port vs best port toward the
                    # sampled intermediate, weighted by path length, using
                    # the same port_occ congestion signal as in-network cost
                    csrc = coords[ep_sw]                        # (E, q)
                    cde = coords[d_ep // conc]
                    cme = coords[mid]
                    src_d = csrc[:, port_dim]                   # (E, q*n)
                    unal_d = src_d != cde[:, port_dim]
                    unal_m = src_d != cme[:, port_dim]
                    min_d = (port_val[None, :] == cde[:, port_dim]) & unal_d
                    min_m = (port_val[None, :] == cme[:, port_dim]) & unal_m
                    occ_e = port_occ[
                        nbr[ep_sw].astype(I32) * IN + in_port_at_nb[ep_sw]
                    ]
                    ok_e = link_ok_t[ep_sw]
                    # a dead/empty candidate set prices as BIGOCC, small enough
                    # that BIGOCC * h_val stays inside int32 for any q
                    BIGOCC = jnp.int32(1 << 24)
                    occ_min = jnp.min(
                        jnp.where(min_d & ok_e, occ_e, BIGOCC), axis=1
                    )
                    occ_val = jnp.min(
                        jnp.where(min_m & ok_e, occ_e, BIGOCC), axis=1
                    )
                    h_min = jnp.sum(csrc != cde, axis=1)
                    h_val = (
                        jnp.sum(csrc != cme, axis=1)
                        + jnp.sum(cme != cde, axis=1)
                    )
                    take_val = occ_val * h_val < occ_min * h_min
                    mid = jnp.where(take_val, mid, NOMID)
                f_imd = f_imd.at[inj_flat].set(mid, mode="drop")
            dlen = dlen.at[jnp.where(do_inj, inj_qi, NQ + 1)].add(1, mode="drop")
            n_injected = state.n_injected + jnp.sum(do_inj)

            # cursor advance for finite injecting ranks
            adv = do_inj & e_fin
            pk2 = jnp.where(adv, e_pk + 1, e_pk)
            move_d = adv & (pk2 >= e_np)
            di2 = jnp.where(move_d, e_di + 1, e_di)
            pk2 = jnp.where(move_d, 0, pk2)
            move_s = move_d & (di2 >= e_deg)
            cs2 = jnp.where(move_s, e_cs + 1, e_cs)
            di2 = jnp.where(move_s, 0, di2)
            # scatter back to rank arrays (each finite rank has exactly 1 endpoint)
            upd = jnp.where((r_of_e >= 0) & e_fin, r_of_e, R + 5)
            cur_step = cs.at[upd].set(cs2, mode="drop")
            dst_i = state.dst_i.at[upd].set(di2, mode="drop")
            pkt_i = state.pkt_i.at[upd].set(pk2, mode="drop")

            # per-epoch delivered / injected counters (epoch 0 on the static path)
            epoch_delivered = state.epoch_delivered.at[ei].add(jnp.sum(tgt_del))
            epoch_injected = state.epoch_injected.at[ei].add(jnp.sum(do_inj))

            new_state = SimState(
                t=t + 1, key=state.key,
                f_dst=f_dst, f_der=f_der, f_hop=f_hop, f_rank=f_rank,
                f_step=f_step, f_birth=f_birth, f_imd=f_imd,
                qhead=qhead, qlen=qlen + dlen, busy=busy,
                cur_step=cur_step, dst_i=dst_i, pkt_i=pkt_i, completed=completed,
                sent=sent, got=got,
                lat_sum=lat_sum, n_delivered=n_delivered, n_injected=n_injected,
                hop_sum=hop_sum, hop_max=hop_max,
                esc_count=esc_count,
                epoch_delivered=epoch_delivered, epoch_injected=epoch_injected,
            )
        if spec is None:
            return new_state

        with jax.named_scope("telemetry"):
            # telemetry probes (enabled engines only)
            # Pure extra accumulation from this cycle's internal signals; none
            # of it feeds back into the physics above.  Window index clamps so
            # cycles past n_windows * window accumulate into the last window.
            wi = jnp.minimum(t // spec.window, spec.n_windows - 1)
            net_move = net
            # fault-epoch probes: a flip is a cycle whose active epoch differs
            # from the previous cycle's; dead_links samples the directed dead
            # count of the active mask each cycle
            if NE == 1:
                flip = jnp.int32(0)
            else:
                ei_prev = (jnp.sum(
                    jnp.maximum(t - 1, 0) >= wt.epoch_start.astype(I32)
                ) - 1).astype(I32)
                flip = ((t > 0) & (ei != ei_prev)).astype(I32)
            dead_now = jnp.sum(~link_ok_t)
            # per-pool occupancy histogram: one sample of every queue per cycle
            occ_hist = jnp.zeros(P * (CAP + 1), dtype=I32).at[
                h_pool.astype(I32) * (CAP + 1) + qlen
            ].add(1)
            # log2 ejection-latency bin per delivered target packet
            lat_bin = jnp.clip(
                jnp.floor(jnp.log2(jnp.maximum(lat_pkt, 1.0))).astype(I32),
                0, spec.lat_bins - 1,
            )
            tel = TelemetryState(
                link_util=tel.link_util.at[wi].add((g1 + g2).reshape(S, OUT)),
                vc_occ=tel.vc_occ.at[wi].add(occ_hist),
                deroutes=tel.deroutes.at[wi].add(
                    jnp.sum(net_move & ~best_min)
                ),
                escalations=tel.escalations.at[wi].add(
                    jnp.sum(net_move & esc_chosen)
                ),
                inflight=tel.inflight.at[wi].add(jnp.sum(qlen)),
                cycles=tel.cycles.at[wi].add(1),
                injected=tel.injected.at[wi].add(jnp.sum(do_inj)),
                delivered=tel.delivered.at[wi].add(jnp.sum(tgt_del)),
                lat_sum=tel.lat_sum.at[wi].add(lat_add),
                lat_hist=tel.lat_hist.at[
                    jnp.where(tgt_del, lat_bin, spec.lat_bins + 1)
                ].add(1, mode="drop"),
                epoch_flips=tel.epoch_flips.at[wi].add(flip),
                dead_links=tel.dead_links.at[wi].add(dead_now),
            )
            return new_state, tel

    return step
