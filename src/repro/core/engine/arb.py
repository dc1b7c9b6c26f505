"""Switch arbitration backends: lax scatter-min reference and Pallas kernel.

One arbitration round resolves, for every queue head in the machine, the
request it posted against one output port of its own switch: per output,
the head carrying the smallest packed key (random bits << head_bits |
global head index, with ``head_bits`` 17 below 2**17 heads a lane and the
least width that holds the heads above; ``tables.head_field_bits`` — unique,
so ties are impossible) wins.  The step kernel runs
two such rounds per cycle (separable allocation with the paper's 2x
internal speedup); this module provides the round primitive

    arbitrate(req_out, packed) -> (won, gcount)

with ``req_out`` the *global* output index ``switch * OUT + port`` (any
value >= S*OUT means "not requesting"), ``won`` the per-head grant mask
and ``gcount`` the per-output grant count (the drain/token update).

Two implementations, selected by ``StaticTables.arb``:

  * ``"lax"`` — the reference: one scatter-min over the flat (S*OUT,)
    grant table, exactly the seed engine's code path;
  * ``"pallas"`` — a ``pallas_call`` with one program instance per
    switch.  Arbitration is switch-local (a head can only request its own
    switch's outputs, and heads are switch-major in queue order), so each
    instance loads its (IN*P*V,) slice of requests/keys, builds the
    (heads, OUT) request matrix in registers/VMEM and takes a masked min
    per output — no scatter at all.  Keys enter as order-preserving int32
    (:func:`order_keys`; Mosaic has no unsigned reductions).  Integer min
    over unique keys is platform-independent, so the kernel is
    **bit-exact** against the lax reference (regression-pinned in
    ``tests/test_arb.py``, interpret mode on CPU CI; compiled on TPU
    where ``interpret=None`` resolves to False).

Both backends vmap (pallas_call has a batching rule that prepends grid
dimensions), so lane-batched grids run unchanged under either.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

I32 = jnp.int32
U32 = jnp.uint32
_INVALID = np.uint32(0xFFFFFFFF)


def arbitrate_lax(req_out, packed, S: int, OUT: int):
    """Reference round: scatter-min grant table over all S*OUT outputs."""
    valid = req_out < S * OUT
    req_safe = jnp.minimum(req_out, S * OUT - 1)
    grant = jnp.full(S * OUT, jnp.uint32(_INVALID))
    grant = grant.at[req_out].min(packed, mode="drop")
    won = valid & (grant[req_safe] == packed)
    gcount = jnp.zeros(S * OUT, dtype=I32).at[
        jnp.where(won, req_out, S * OUT + 1)
    ].add(1, mode="drop")
    return won, gcount


def order_keys(packed):
    """uint32 keys -> int32 with the same order (``key ^ 2**31``, bitcast).

    Mosaic reduces signed integers only; the flip maps uint32 order onto
    int32 order, so an int32 min picks the same winner.
    """
    return jax.lax.bitcast_convert_type(packed ^ jnp.uint32(1 << 31), I32)


# order_keys(_INVALID): above every real key, which is < 2**32 - 1
INVALID_KEY = np.int32(np.iinfo(np.int32).max)


def _arb_kernel(local_ref, key_ref, won_ref, gcnt_ref, *, OUT: int):
    """One switch: masked min per output over this switch's queue heads.

    Heads run down the sublanes and outputs along the lanes: per-head
    values are ``(HS, 1)`` columns, per-output values ``(1, OUT)`` rows.
    """
    lp = local_ref[0]                        # (HS, 1) local port, -1 = none
    key = key_ref[0]                         # (HS, 1) ordered int32, unique
    HS = lp.shape[0]
    oid = jax.lax.broadcasted_iota(I32, (HS, OUT), 1)
    req = lp == oid                          # (HS, OUT) request matrix
    vals = jnp.where(req, key, INVALID_KEY)
    grant = vals.min(axis=0, keepdims=True)  # (1, OUT) winning key
    won = req & (key == grant)
    won_ref[0] = won.any(axis=1, keepdims=True).astype(I32)
    gcnt_ref[0] = won.astype(I32).sum(axis=0, keepdims=True)


def make_arbiter(
    S: int, OUT: int, H: int, arb: str, interpret: bool | None = None
) -> Callable:
    """Build the round primitive for one static configuration.

    ``H`` must be switch-major divisible (H == S * heads_per_switch, the
    engine's queue layout).  ``interpret=None`` resolves per-backend:
    interpret off TPU (CPU CI), compiled on TPU.
    """
    if arb == "lax":
        def arbiter(req_out, packed):
            return arbitrate_lax(req_out, packed, S, OUT)
        return arbiter
    if arb != "pallas":
        raise ValueError(f"unknown arbitration backend {arb!r} "
                         "(expected 'lax' or 'pallas')")
    if H % S:
        raise ValueError(f"H={H} not divisible by S={S}")
    HS = H // S
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    sw = jnp.asarray(np.arange(H) // HS, dtype=I32)  # switch of each head
    # one program per switch; the unit dims keep every block's two minor
    # dims equal to the array's, as Mosaic's (8, 128) tiling requires
    head = pl.BlockSpec((1, HS, 1), lambda s: (s, 0, 0))
    call = pl.pallas_call(
        functools.partial(_arb_kernel, OUT=OUT),
        grid=(S,),
        in_specs=[head, head],
        out_specs=[head, pl.BlockSpec((1, 1, OUT), lambda s: (s, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((S, HS, 1), I32),
                   jax.ShapeDtypeStruct((S, 1, OUT), I32)],
        interpret=interpret,
        name="arbitrate",
    )

    def arbiter(req_out, packed):
        # local port within the head's own switch; -1 never matches an output
        local = jnp.where(
            req_out < S * OUT, req_out - sw * OUT, -1
        ).astype(I32)
        won, g = call(local.reshape(S, HS, 1),
                      order_keys(packed).reshape(S, HS, 1))
        return won.reshape(H).astype(bool), g.reshape(S * OUT)

    return arbiter
