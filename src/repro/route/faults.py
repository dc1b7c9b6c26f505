"""Link-fault masks for fault-aware routing.

A fault mask is an ``(S, q*n)`` bool array over the engine's dense
*directed* network ports (True = healthy): port ``d*n + v`` of switch
``s`` is the link toward coordinate value ``v`` in dimension ``d``.
Self-loop ports (``v == coords[s, d]``) are never candidates and stay
True.  The mask is **per-workload device data**: it rides in
``WorkloadTables`` (see ``Workload.link_ok``), so a fault-scenario grid
batches through one compilation and one device call per shape bucket like
any other workload axis.

Kernel semantics (all policies): candidate sets exclude dead links; when a
minimal-only policy (min/val/ugal) finds every minimal port of the current
switch dead, deroutes *escalate* — non-minimal ports in unaligned
dimensions become legal while the per-packet budget ``m`` lasts.  The
budget bound keeps worst-case hops inside each policy's declared VC
budget, preserving hop-indexed-VC deadlock freedom under faults
(arXiv 2404.04315's key constraint).  Omni-WAR needs no escalation: its
candidate set already contains the deroutes.
"""

from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Iterable, Sequence

import numpy as np

from repro.core.hyperx import HyperX
from repro.route.topology import dst_switch_table, self_port_mask

if TYPE_CHECKING:  # pragma: no cover
    from repro.traffic.workload import Workload


def no_faults(topo: HyperX) -> np.ndarray:
    """All-healthy mask — the default every workload gets."""
    return np.ones((topo.num_switches, topo.q * topo.n), dtype=bool)


def fail_links(
    topo: HyperX,
    links: Iterable[tuple[int, int]],
    mask: np.ndarray | None = None,
    bidirectional: bool = True,
) -> np.ndarray:
    """Kill switch-to-switch links given as (src, dst) switch-id pairs.

    Pairs must be at Hamming distance exactly 1.  ``bidirectional``
    (default) kills the reverse direction too — a dead cable, the common
    failure unit.  Mutates and returns ``mask`` (fresh all-healthy mask
    when None).
    """
    if mask is None:
        mask = no_faults(topo)
    coords = topo.all_switch_coords()
    n = topo.n
    for a, b in links:
        diff = np.flatnonzero(coords[a] != coords[b])
        if len(diff) != 1:
            raise ValueError(
                f"switches {a} and {b} are not neighbours "
                f"(Hamming distance {len(diff)})"
            )
        d = int(diff[0])
        mask[a, d * n + coords[b, d]] = False
        if bidirectional:
            mask[b, d * n + coords[a, d]] = False
    return mask


def fail_switches(topo: HyperX, switches: Sequence[int]) -> np.ndarray:
    """Kill every link touching the given switches (switch power-off)."""
    mask = no_faults(topo)
    switches = np.asarray(switches, dtype=np.int64)
    mask[switches, :] = False
    # incoming directions: any port whose destination is a dead switch
    dst = dst_switch_table(topo.all_switch_coords(), topo.n, topo.q)
    dead = np.zeros(topo.num_switches, dtype=bool)
    dead[switches] = True
    mask[dead[dst].reshape(mask.shape)] = False
    return mask


def random_link_faults(
    topo: HyperX, rate: float, seed: int = 0
) -> np.ndarray:
    """Fail each undirected cable independently with probability ``rate``."""
    if not 0.0 <= rate <= 1.0:
        raise ValueError(f"fault rate must be in [0, 1], got {rate}")
    rng = np.random.default_rng(seed)
    cables = topo.link_array()                      # (L, 2) undirected
    dead = cables[rng.random(len(cables)) < rate]
    return fail_links(topo, [tuple(map(int, c)) for c in dead])


def faults_from_endpoints(
    topo: HyperX,
    endpoints: Sequence[int],
    links_per_endpoint: int = 1,
    seed: int = 0,
) -> np.ndarray:
    """Network faults implied by endpoint failures (scheduler churn).

    Failure domains are co-packaged: an endpoint failure (node loss)
    takes ``links_per_endpoint`` cables adjacent to its switch with it —
    chosen deterministically per endpoint id, so every strategy facing
    the same physical churn sees the same dead network.  A switch whose
    endpoints have ALL failed is treated as powered off entirely.
    """
    mask = no_faults(topo)
    endpoints = np.asarray(endpoints, dtype=np.int64)
    if endpoints.size == 0:
        return mask
    coords = topo.all_switch_coords()
    valid = self_port_mask(coords, topo.n, topo.q)
    dst = dst_switch_table(coords, topo.n, topo.q).reshape(valid.shape)
    for ep in np.unique(endpoints):
        sw = int(ep) // topo.concentration
        ports = np.flatnonzero(valid[sw])
        rng = np.random.default_rng(seed + int(ep))
        for p in rng.choice(ports, size=min(links_per_endpoint, len(ports)),
                            replace=False):
            fail_links(topo, [(sw, int(dst[sw, p]))], mask=mask)
    switches, counts = np.unique(
        endpoints // topo.concentration, return_counts=True
    )
    fully_dead = switches[counts >= topo.concentration]
    if fully_dead.size:
        mask &= fail_switches(topo, fully_dead)
    return mask


# ------------------------------------------------------------- derived data
def intermediate_pool(
    topo: HyperX, link_ok: np.ndarray
) -> tuple[np.ndarray, int]:
    """Healthy Valiant-intermediate switches as a fixed-shape device table.

    A switch qualifies while it keeps at least one healthy real (non-self)
    port in each direction — enterable and exitable.  Returns
    ``(pool, count)`` where ``pool`` is (S,) int32, the qualifying ids
    cyclically repeated to length S: the *shape* is static (one compile
    per topology) while the *values* are per-workload, so fault grids
    vmap without retracing.
    """
    link_ok = np.asarray(link_ok, dtype=bool)
    coords = topo.all_switch_coords()
    valid = self_port_mask(coords, topo.n, topo.q)
    out_ok = (link_ok & valid).any(axis=1)
    dst = dst_switch_table(coords, topo.n, topo.q).reshape(valid.shape)
    in_ok = np.zeros(topo.num_switches, dtype=bool)
    healthy_dirs = link_ok & valid
    np.logical_or.at(in_ok, dst[healthy_dirs], True)
    ids = np.flatnonzero(out_ok & in_ok)
    if ids.size == 0:
        ids = np.array([0], dtype=np.int64)   # degenerate machine; unused
    pool = np.resize(ids, topo.num_switches).astype(np.int32)
    return pool, int(min(ids.size, topo.num_switches))


def is_connected(topo: HyperX, link_ok: np.ndarray) -> bool:
    """True when every switch is reachable from switch 0 over healthy
    directed links — the sanity check fault-injection tests use."""
    coords = topo.all_switch_coords()
    valid = self_port_mask(coords, topo.n, topo.q)
    dst = dst_switch_table(coords, topo.n, topo.q).reshape(valid.shape)
    ok = np.asarray(link_ok, dtype=bool) & valid
    seen = np.zeros(topo.num_switches, dtype=bool)
    seen[0] = True
    frontier = [0]
    while frontier:
        s = frontier.pop()
        for t in dst[s][ok[s]]:
            if not seen[t]:
                seen[t] = True
                frontier.append(int(t))
    return bool(seen.all())


def apply_faults(wl: "Workload", link_ok: np.ndarray) -> "Workload":
    """A copy of ``wl`` carrying the fault mask (lowered into
    ``WorkloadTables.link_ok`` by the engine's prepare step)."""
    link_ok = np.asarray(link_ok, dtype=bool)
    expect = (wl.topo.num_switches, wl.topo.q * wl.topo.n)
    if link_ok.shape != expect:
        raise ValueError(
            f"fault mask shape {link_ok.shape} != {expect} for {wl.topo}"
        )
    return dataclasses.replace(wl, link_ok=link_ok)
