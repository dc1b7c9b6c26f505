"""Static compile-time structure of the simulator.

Everything here depends only on the *configuration* of a simulation — the
topology, routing policy, VC-pool count, deroute budget, and queue capacity
— never on the workload.  The tables are baked into the jit closure as
trace constants (they are genuinely constant across a sweep), while
everything per-workload lives in :mod:`repro.core.engine.workload_tables`
(including link-fault masks and Valiant intermediate pools) and is passed
to the compiled step function as device *arguments*.

The routing ``mode`` string resolves through the :mod:`repro.route`
registry: the policy declares its hop-indexed VC budget (which sizes the
queue space — deadlock freedom) and the static predicates the step kernel
specializes on.  Unknown modes raise with the registered policy names.

``build_static_tables`` is memoised on its full key, so every simulator /
engine construction for the same ``(topo, mode, P, m, cap, penalty)``
configuration shares one table set — and therefore one XLA compilation of
the step function.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.core.engine.packing import pack
from repro.core.hyperx import HyperX
from repro.route import get_policy, neighbor_tables, port_layout

# The packed arbitration key (step.py) is one uint32: a random draw in the
# high bits, the head index in the low ``head_bits``, so keys are unique and
# the smallest draw wins an output.  The head field is MIN_HEAD_BITS wide
# for every machine under 2**17 heads a lane (their programs and random
# streams are those of a fixed 17-bit field) and just wide enough above
# that, at the cost of random bits.  Below MIN_RANDOM_BITS random bits,
# equal draws at the minimum grow common and each goes to the lower head
# index, so arbitration would favour low-numbered queues: such machines are
# refused (H < 2**24).
MIN_HEAD_BITS = 17
MIN_RANDOM_BITS = 8


def head_field_bits(H: int) -> int:
    """Width of the packed key's head field for ``H`` heads a lane: the
    least width >= MIN_HEAD_BITS that holds every index below ``H`` (so no
    key reaches 2**32 - 1, the arbiters' "no request")."""
    hb = max(MIN_HEAD_BITS, H.bit_length())
    if 32 - hb < MIN_RANDOM_BITS:
        raise ValueError(
            f"{H} queue heads per lane leave {32 - hb} random bits in the "
            f"packed arbitration key (random bits << {hb} | head), fewer "
            f"than {MIN_RANDOM_BITS}")
    return hb

I32 = jnp.int32


class StaticTables(NamedTuple):
    """Topology / port / VC constant tables + static dimensions.

    Shapes (S switches, E endpoints, IN=OUT ports/switch, P pools, V VCs):
      coords          (S, q)     switch coordinates
      nbr             (S, q*n)   neighbour switch per network port
      in_port_at_nb   (S, q*n)   arrival port at that neighbour
      port_dim/val    (q*n,)     dimension / value addressed by each port
      h_pool, h_sw    (H,)       queue-head index decomposition (H == NQ)
      inj_base        (E,)       injection queue base index (pool 0, VC 0)
      ep_sw           (E,)       switch hosting each endpoint
    """

    # dimensions (Python ints / strings — static under jit)
    n: int
    q: int
    conc: int
    S: int
    E: int
    IN: int
    OUT: int
    P: int
    V: int
    NQ: int
    H: int
    head_bits: int    # head field of the packed arbitration key
    CAP: int
    m: int            # deroute budget
    PEN: int          # deroute penalty on the cost scale
    mode: str         # registered routing-policy name
    arb: str          # arbitration backend: "lax" scatter-min | "pallas"
    kernel: str       # route+arbitrate block: "lax" | "pallas" megakernel
    # device constant tables
    coords: jnp.ndarray
    nbr: jnp.ndarray
    in_port_at_nb: jnp.ndarray
    port_dim: jnp.ndarray
    port_val: jnp.ndarray
    h_pool: jnp.ndarray
    h_sw: jnp.ndarray
    inj_base: jnp.ndarray
    ep_sw: jnp.ndarray


@functools.lru_cache(maxsize=None)
def build_static_tables(
    topo: HyperX,
    mode: str = "omniwar",
    num_pools: int = 1,
    max_deroutes: int | None = None,
    cap: int = 8,
    penalty_packets: int = 4,
    arb: str = "lax",
    pack_tables: bool = True,
    kernel: str = "lax",
) -> StaticTables:
    """Construct (and cache) the constant tables for one configuration.

    ``arb`` selects the arbitration backend the step kernel is built with
    ("lax" scatter-min reference or the "pallas" per-switch kernel — bit
    identical, regression-pinned).  ``kernel`` selects the route+arbitrate
    block implementation: "lax" keeps the reference jnp path; "pallas"
    swaps in the fused per-switch megakernel (candidate masks, cost,
    argmin and both arbitration rounds in one ``pallas_call`` — bit
    identical, regression-pinned; subsumes ``arb`` for those rounds).
    ``pack_tables`` packs the small-range
    lookup tables to int8/int16 with topology-derived bounds (the step
    kernel widens to int32 at each gather); ``False`` keeps the int32
    reference layout for the packing parity tests.
    """
    policy = get_policy(mode)  # raises with registered names when unknown
    n, q, conc = topo.n, topo.q, topo.concentration
    S = topo.num_switches
    E = topo.num_endpoints
    IN = q * n + conc          # network input ports (dense dim*val) + injection
    OUT = q * n + conc         # network output ports + ejection per offset
    P = num_pools
    m = policy.default_deroutes(q) if max_deroutes is None else max_deroutes
    V = policy.vc_budget(q, m)  # hop-indexed VCs (deadlock freedom)
    NQ = S * IN * P * V
    H = NQ                     # one potential head per queue
    head_bits = head_field_bits(H)

    coords_np = topo.all_switch_coords()                       # (S, q)
    nbr, in_port_at_nb = neighbor_tables(coords_np, n, q)
    port_dim, port_val = port_layout(n, q)

    h_idx = np.arange(H, dtype=np.int64)
    h_pool_np = (h_idx // V) % P
    h_sw_np = h_idx // (V * P * IN)

    # endpoint -> injection queue (pool of its rank added at runtime, VC 0)
    e_ids = np.arange(E)
    e_sw = e_ids // conc
    e_port = q * n + (e_ids % conc)
    inj_base_np = ((e_sw * IN + e_port) * P) * V

    if pack_tables:
        # bounds are topology-derived (never data-derived): same config =>
        # same dtypes => one jit cache entry, regardless of workload values
        def lower(a, bound):
            return jnp.asarray(pack(a, bound))
    else:
        def lower(a, bound):
            return jnp.asarray(a, dtype=I32)

    return StaticTables(
        n=n, q=q, conc=conc, S=S, E=E, IN=IN, OUT=OUT, P=P, V=V,
        NQ=NQ, H=H, head_bits=head_bits, CAP=cap, m=m,
        PEN=penalty_packets * 8,  # cost scale: occupancy*8 + jitter(3 bits)
        mode=mode, arb=arb, kernel=kernel,
        coords=lower(coords_np, n - 1),
        nbr=lower(nbr, S - 1),
        in_port_at_nb=lower(in_port_at_nb, IN - 1),
        port_dim=lower(port_dim, q - 1),
        port_val=lower(port_val, n - 1),
        h_pool=lower(h_pool_np, P - 1),
        h_sw=lower(h_sw_np, S - 1),
        inj_base=lower(inj_base_np, NQ - 1),
        ep_sw=lower(e_sw, S - 1),
    )


def onward_index(st: StaticTables) -> np.ndarray:
    """``(n, S, q*n)`` flat indices into a ``(S, q*n)`` link mask: entry
    ``[w, s, p]`` is the link from the neighbour behind port ``p`` of
    switch ``s`` toward value ``w`` in that port's dimension."""
    nbr = np.asarray(st.nbr, dtype=np.int32)
    pdim = np.asarray(st.port_dim, dtype=np.int32)
    w = np.arange(st.n, dtype=np.int32)[:, None, None]
    return nbr[None] * (st.q * st.n) + pdim[None, None, :] * st.n + w
