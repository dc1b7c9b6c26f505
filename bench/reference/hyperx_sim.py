"""Plain reference of the HyperX cycle simulator, in numpy.

One lane at a time, one packet-time per Python iteration, written from the
model's description rather than from the engine's code paths:

  * machine: a q-D HyperX of side n, ``conc`` endpoints per switch; switch
    ids are mixed-radix, slowest dimension first; network port ``d*n + v``
    of a switch leads to the switch whose dimension ``d`` is set to ``v``
    (the port whose value is the switch's own coordinate is no link);
  * queues: input-queued FIFO rings of ``cap`` packets per (switch, input
    port, hop-indexed VC), ``V = q + m + 1`` VCs for Omni-WAR routing with
    deroute budget ``m``; one VC pool (the shared fabric), healthy links;
  * routing: Omni-WAR -- any port of an unaligned dimension while the
    packet has deroutes left, a minimal one otherwise; cost = 8 x the
    occupancy of the downstream input port + a penalty for a deroute +
    3 bits of random jitter; lowest cost wins, the first port on ties;
  * allocation: two rounds of random separable arbitration per cycle (the
    2x internal speedup); per output the request with the smallest random
    key wins; an output's token bucket (2 tokens, one drained per cycle)
    holds the sustained link rate at one packet per packet-time;
  * flow control: a packet moves only into a downstream queue with room
    (lossless); ejection is free of flow control;
  * ranks walk step tables (send ``npkts`` to each destination of a step,
    complete the step once every send is delivered and ``recv_need``
    packets of that step arrived; at most ``window`` incomplete steps);
    infinite ranks (background) inject one packet per cycle whenever their
    injection queue has room.

The random draws of each cycle are JAX's threefry streams (``fold_in`` of
the lane seed's key with the cycle, split three ways), so the reference
and the engine see the same coin flips and must agree bit for bit.

``break_link_rate=True`` is the control: the same model with the link
rate guarantee broken (output tokens are never charged), which must fail
the comparison.
"""

from __future__ import annotations

import dataclasses

import numpy as np

BIG = np.int32(1 << 28)


@dataclasses.dataclass(frozen=True)
class Machine:
    n: int
    q: int
    conc: int
    cap: int = 8
    deroutes: int | None = None   # Omni-WAR budget m; None = q
    penalty_packets: int = 4

    @property
    def S(self):
        return self.n ** self.q

    @property
    def E(self):
        return self.S * self.conc

    @property
    def QN(self):
        return self.q * self.n

    @property
    def PORTS(self):
        return self.QN + self.conc

    @property
    def m(self):
        return self.q if self.deroutes is None else self.deroutes

    @property
    def V(self):
        return self.q + self.m + 1

    @property
    def NQ(self):
        return self.S * self.PORTS * self.V


CONFIG_KEYS = {"topology", "routing", "fabric_partitioning", "engine"}
# keys that describe a configuration and change nothing it runs
DESCRIPTIVE_KEYS = {"name", "source", "deployment", "reduced", "assumed",
                    "guarantees"}
# engine options the reference models
MODELLED_ENGINE = {"cap", "penalty_packets", "max_deroutes"}
# engine options that select an implementation with the same results
SAME_RESULTS_ENGINE = {"arb", "kernel", "chunk", "canon", "pack", "bucket"}


def machine_from_config(config: dict) -> Machine:
    """The machine a configuration file states.  Refuses any routing,
    fabric, fault or engine setting the reference does not model."""
    extra = sorted(set(config) - CONFIG_KEYS - DESCRIPTIVE_KEYS)
    if extra:
        raise ValueError(f"the reference does not model configuration "
                         f"keys {extra}")
    if config["routing"] != "omniwar":
        raise ValueError(f"the reference models only omniwar routing, not "
                         f"{config['routing']!r}")
    if config["fabric_partitioning"] != "shared":
        raise ValueError(f"the reference models only the shared fabric, not "
                         f"{config['fabric_partitioning']!r}")
    topo = config["topology"]
    if set(topo) != {"n", "q", "concentration"}:
        raise ValueError(f"the reference models a HyperX of n, q and "
                         f"concentration only, not {sorted(topo)}")
    eng = config.get("engine", {})
    extra = sorted(set(eng) - MODELLED_ENGINE - SAME_RESULTS_ENGINE)
    if extra:
        raise ValueError(f"the reference does not model engine options "
                         f"{extra}")
    return Machine(n=topo["n"], q=topo["q"], conc=topo["concentration"],
                   cap=eng.get("cap", 8), deroutes=eng.get("max_deroutes"),
                   penalty_packets=eng.get("penalty_packets", 4))


@dataclasses.dataclass
class Lane:
    """One scenario: ranks, their endpoints and their step tables."""

    rank_ep: np.ndarray     # (R,) endpoint of each rank
    infinite: np.ndarray    # (R,) bool: background source, never completes
    window: np.ndarray      # (R,) outstanding-step window
    start: np.ndarray       # (R,) first cycle a rank may inject
    dst: np.ndarray         # (R, T, D) destination rank, -1 = none
    npkts: np.ndarray       # (R, T, D) packets per destination
    deg: np.ndarray         # (R, T) destinations per step
    recv_need: np.ndarray   # (R, T) packets to receive before a step is done

    @property
    def warmup(self) -> int:
        return int(self.start.max())


class Draws:
    """Per-cycle random draws (jitter, arbitration keys): JAX's threefry,
    computed on the host CPU ``block`` cycles at a time."""

    def __init__(self, H: int, QN: int, block: int = 32):
        import jax
        import jax.numpy as jnp

        def one(key, t):
            k_arb, k_jit, _k_smp = jax.random.split(jax.random.fold_in(key, t), 3)
            jitter = jax.random.randint(k_jit, (H, QN), 0, 8, dtype=jnp.int32)
            arb = jax.random.bits(k_arb, (H,), dtype=jnp.uint32) >> 17
            return jitter.astype(jnp.int8), arb.astype(jnp.uint16)

        def block_of(seed, t0):
            ts = t0 + jnp.arange(block, dtype=jnp.int32)
            return jax.vmap(one, in_axes=(None, 0))(jax.random.PRNGKey(seed), ts)

        self.block = block
        self._cpu = jax.devices("cpu")[0]
        self._fn = jax.jit(block_of)
        self._jax, self._jnp = jax, jnp
        self._key = None

    def __call__(self, seed: int, t: int):
        t0 = t - t % self.block
        if self._key != (seed, t0):
            with self._jax.default_device(self._cpu):
                out = self._fn(self._jnp.int32(seed), self._jnp.int32(t0))
                self._jit, self._arb = (np.asarray(x) for x in out)
            self._key = (seed, t0)
        i = t - t0
        return self._jit[i].astype(np.int32), self._arb[i].astype(np.uint32)


def _arbitrate(req, packed, n_out):
    """Per output, the request with the smallest key wins."""
    valid = req < n_out
    grant = np.full(n_out, np.iinfo(np.uint32).max, dtype=np.uint32)
    np.minimum.at(grant, req[valid], packed[valid])
    won = np.zeros(len(req), dtype=bool)
    won[valid] = grant[req[valid]] == packed[valid]
    g = np.bincount(req[won], minlength=n_out).astype(np.int32)
    return won, g


def simulate(mc: Machine, lane: Lane, seed: int, horizon: int,
             break_link_rate: bool = False, draws: Draws | None = None
             ) -> dict:
    """Run one lane to completion (or the horizon); return its result."""
    n, q, conc, V, CAP = mc.n, mc.q, mc.conc, mc.V, mc.cap
    S, E, QN, PORTS, NQ, m = mc.S, mc.E, mc.QN, mc.PORTS, mc.NQ, mc.m
    PEN = np.int32(mc.penalty_packets * 8)
    H = NQ
    NOUT = S * PORTS
    R, T, _ = lane.dst.shape
    if draws is None:
        draws = Draws(H, QN)

    # --- machine tables ---------------------------------------------------
    w = n ** np.arange(q - 1, -1, -1)
    coords = (np.arange(S)[:, None] // w[None, :]) % n          # (S, q)
    p_dim = np.repeat(np.arange(q), n)
    p_val = np.tile(np.arange(n), q)
    nbr = (np.arange(S)[:, None]
           + (p_val[None, :] - coords[:, p_dim]) * w[p_dim][None, :])
    arr_port = p_dim[None, :] * n + coords[:, p_dim]           # at the nbr
    h_sw = np.arange(H) // (V * PORTS)
    cur_c = coords[h_sw][:, p_dim]                               # (H, QN)
    nb = nbr[h_sw]
    down_port = nb * PORTS + arr_port[h_sw]                      # (H, QN)
    down_q0 = down_port * V
    out_net = h_sw[:, None] * PORTS + np.arange(QN)[None, :]
    e_sw = np.arange(E) // conc
    inj_q0 = (e_sw * PORTS + QN + np.arange(E) % conc) * V

    ep_rank = np.full(E, -1, dtype=np.int64)
    ep_rank[lane.rank_ep] = np.arange(R)
    finite = ~lane.infinite
    total_sends = lane.npkts.sum(axis=2)

    # --- state ------------------------------------------------------------
    f_dst = np.zeros((NQ, CAP), np.int64)
    f_der = np.zeros((NQ, CAP), np.int64)
    f_hop = np.zeros((NQ, CAP), np.int64)
    f_rank = np.zeros((NQ, CAP), np.int64)
    f_step = np.zeros((NQ, CAP), np.int64)
    f_birth = np.zeros((NQ, CAP), np.int64)
    qhead = np.zeros(NQ, np.int64)
    qlen = np.zeros(NQ, np.int64)
    busy = np.zeros(NOUT, np.int64)
    cur_step = np.zeros(R, np.int64)
    dst_i = np.zeros(R, np.int64)
    pkt_i = np.zeros(R, np.int64)
    completed = np.zeros(R, np.int64)
    sent = np.zeros((R, T), np.int64)
    got = np.zeros((R, T), np.int64)
    lat_sum = np.float32(0.0)
    delivered = injected = hop_sum = hop_max = 0

    def all_done():
        return bool(np.all(completed[finite] >= T))

    t = 0
    while t < horizon and not all_done():
        jitter, arb_key = draws(seed, t)
        port_occ = qlen.reshape(S * PORTS, V).sum(axis=1)
        busy_dec = np.maximum(busy - 1, 0)

        # heads: only non-empty queues can request anything
        hi = np.flatnonzero(qlen > 0)
        ai = np.arange(len(hi))
        hq = qhead[hi]
        packed = (arb_key[hi].astype(np.uint32) << np.uint32(17)) \
            | hi.astype(np.uint32)
        dst, der, hop = f_dst[hi, hq], f_der[hi, hq], f_hop[hi, hq]
        sw = h_sw[hi]
        dsw, dof = dst // conc, dst % conc
        at_dst = sw == dsw
        vcn = np.minimum(hop + 1, V - 1)

        # route: Omni-WAR candidates and cost
        cc = cur_c[hi]
        dst_c = coords[dsw][:, p_dim]
        unaligned = cc != dst_c
        is_min = (p_val[None, :] == dst_c) & unaligned
        qi_down = down_q0[hi] + vcn[:, None]
        on = out_net[hi]
        legal = unaligned & (p_val[None, :] != cc) \
            & (is_min | (der[:, None] > 0)) \
            & (qlen[qi_down] < CAP) & (busy_dec[on] < 2)
        cost = port_occ[down_port[hi]] * 8 + PEN * (~is_min) + jitter[hi]
        cost = np.where(legal, cost, BIG)
        best = np.argmin(cost, axis=1)
        has_port = cost[ai, best] < BIG

        out_g = sw * PORTS + np.where(at_dst, QN + dof, best)
        requesting = (at_dst | has_port) & (busy_dec[out_g] < 2)
        won1, g1 = _arbitrate(np.where(requesting, out_g, NOUT), packed, NOUT)

        qi_best1 = qi_down[ai, best]
        arr1 = np.bincount(qi_best1[won1 & ~at_dst], minlength=NQ)
        tokens = (2 - busy_dec) - g1
        cost2 = np.where(
            legal & (tokens[on] > 0) & (qlen[qi_down] + arr1[qi_down] < CAP),
            cost, BIG)
        best2 = np.argmin(cost2, axis=1)
        has2 = cost2[ai, best2] < BIG
        ej_ok = tokens[sw * PORTS + QN + dof] > 0
        out2 = sw * PORTS + np.where(at_dst, QN + dof, best2)
        req2 = requesting & ~won1 & np.where(at_dst, ej_ok, has2)
        won2, g2 = _arbitrate(np.where(req2, out2, NOUT), packed, NOUT)
        won = won1 | won2

        chosen = np.where(won2, best2, best)
        qi_best = qi_down[ai, chosen]
        bmin = is_min[ai, chosen]

        busy = busy_dec if break_link_rate else busy_dec + g1 + g2

        # dequeue winners
        qhead_old = qhead.copy()
        qhead[hi[won]] = (hq[won] + 1) % CAP
        dlen = np.zeros(NQ, np.int64)
        dlen[hi[won]] = -1

        # ejections
        eject = won & at_dst
        rank, pstep, birth = f_rank[hi, hq], f_step[hi, hq], f_birth[hi, hq]
        e_src = eject & finite[rank]
        np.add.at(sent, (rank[e_src], pstep[e_src]), 1)
        drank = ep_rank[dst]
        e_dst = eject & (drank >= 0) & finite[np.maximum(drank, 0)]
        np.add.at(got, (drank[e_dst], pstep[e_dst]), 1)
        lat_sum = np.float32(lat_sum + np.float32((t - birth[e_src]).sum()))
        hop_sum += int(hop[e_src].sum())
        delivered += int(e_src.sum())
        if eject.any():
            hop_max = max(hop_max, int(hop[eject].max()))

        # network moves: a round-2 arrival lands behind a round-1 arrival
        net = won & ~at_dst
        tq = qi_best[net]
        ts = (qhead_old[tq] + qlen[tq] + (won2 * arr1[qi_best])[net]) % CAP
        f_dst[tq, ts] = dst[net]
        f_der[tq, ts] = der[net] - (~bmin[net])
        f_hop[tq, ts] = hop[net] + 1
        f_rank[tq, ts] = rank[net]
        f_step[tq, ts] = pstep[net]
        f_birth[tq, ts] = birth[net]
        np.add.at(dlen, tq, 1)

        # step completion
        rr = np.arange(R)
        for _ in range(4):
            c = np.minimum(completed, T - 1)
            comp = (completed >= T) | (
                (sent[rr, c] >= total_sends[rr, c])
                & (got[rr, c] >= lane.recv_need[rr, c]))
            completed = completed + (finite & (completed < T) & comp)
        cs = cur_step
        cs = cs + (finite & (cs < T)
                   & (lane.deg[rr, np.minimum(cs, T - 1)] == 0))

        # injection
        r_e = np.maximum(ep_rank, 0)
        has_rank = ep_rank >= 0
        e_fin = finite[r_e]
        e_cs = np.where(e_fin, cs[r_e], 0)
        e_di = np.where(e_fin, dst_i[r_e], 0)
        e_pk = np.where(e_fin, pkt_i[r_e], 0)
        e_t = np.minimum(e_cs, T - 1)
        e_deg = lane.deg[r_e, e_t]
        e_np = lane.npkts[r_e, e_t, e_di]
        in_win = e_cs < np.minimum(T, completed[r_e] + lane.window[r_e])
        work = np.where(e_fin, (e_cs < T) & (e_di < e_deg) & in_win, True)
        work &= t >= lane.start[r_e]
        iq = inj_q0
        do_inj = has_rank & work & (qlen[iq] + dlen[iq] < CAP)

        d_rank = np.clip(lane.dst[r_e, e_t, e_di], 0, R - 1)
        iqs, islot = iq[do_inj], (qhead_old[iq] + qlen[iq])[do_inj] % CAP
        f_dst[iqs, islot] = lane.rank_ep[d_rank][do_inj]
        f_der[iqs, islot] = m
        f_hop[iqs, islot] = 0
        f_rank[iqs, islot] = r_e[do_inj]
        f_step[iqs, islot] = e_cs[do_inj]
        f_birth[iqs, islot] = t
        np.add.at(dlen, iqs, 1)
        injected += int(do_inj.sum())

        adv = do_inj & e_fin
        pk2 = np.where(adv, e_pk + 1, e_pk)
        move_d = adv & (pk2 >= e_np)
        di2 = np.where(move_d, e_di + 1, e_di)
        pk2 = np.where(move_d, 0, pk2)
        move_s = move_d & (di2 >= e_deg)
        cs2 = np.where(move_s, e_cs + 1, e_cs)
        di2 = np.where(move_s, 0, di2)
        upd = has_rank & e_fin
        cur_step = cs.copy()
        cur_step[r_e[upd]] = cs2[upd]
        dst_i[r_e[upd]] = di2[upd]
        pkt_i[r_e[upd]] = pk2[upd]

        qlen = qlen + dlen
        t += 1

    stranded = int(qlen.sum())
    makespan = t - lane.warmup
    return {
        "makespan": makespan,
        "makespan_cycles": makespan * 16,
        "delivered": delivered,
        "injected": injected,
        "avg_latency": float(lat_sum) / max(delivered, 1),
        "avg_hops": float(hop_sum) / max(delivered, 1),
        "completed": all_done(),
        "max_hops": hop_max,
        "reescalated": 0,   # forced fault escapes: no link is down
        "stranded": stranded,
        "ejected": injected - stranded,
        "epoch_delivered": (delivered,),
        "epoch_injected": (injected,),
    }
