"""Mamba-2 SSD chunked scan — Pallas TPU kernel.

One program instance processes one (batch, head, chunk) tile:

    y_diag = (C B^T ∘ decay) · (dt x)          intra-chunk, MXU matmuls
    y_off  = (C h_in^T) ∘ exp(cum)             incoming-state contribution
    h_out  = h_in * exp(cum[-1]) + B^T · ((dt x) ∘ decay_states)

The chunk grid dimension is innermost and sequential; the (P, N) state
lives in VMEM scratch and carries across chunks — the TPU-native
re-expression of the CUDA kernel's inter-block state passing.  All
matmul operands are padded by the wrapper to MXU-aligned sizes
(chunk, P, N multiples of 128 where it matters).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref, state_ref, *,
            nc, L):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)            # (L, P)
    dt = dt_ref[0, 0].astype(jnp.float32)          # (L,)
    A = a_ref[0].astype(jnp.float32)               # scalar
    Bm = b_ref[0].astype(jnp.float32)              # (L, N)
    Cm = c_ref[0].astype(jnp.float32)              # (L, N)

    dA = dt * A                                    # (L,) log-decay, <= 0
    cum = jnp.cumsum(dA)                           # (L,)
    seg = cum[:, None] - cum[None, :]              # (L, L)
    tri = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    decay = jnp.where(tri, jnp.exp(seg), 0.0)      # (L, L)

    xd = x * dt[:, None]                           # (L, P) discretized
    scores = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                              # (L, L)
    y = jax.lax.dot_general(
        scores * decay, xd, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                              # (L, P)

    h_in = state_ref[...]                          # (N, P)
    y_off = jax.lax.dot_general(
        Cm, h_in, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ) * jnp.exp(cum)[:, None]                      # (L, P)
    y_ref[0, 0] = (y + y_off).astype(y_ref.dtype)

    decay_states = jnp.exp(cum[-1] - cum)          # (L,)
    h_new = h_in * jnp.exp(cum[-1]) + jax.lax.dot_general(
        Bm, xd * decay_states[:, None], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                              # (N, P)
    state_ref[...] = h_new

    @pl.when(ic == nc - 1)
    def _emit_state():
        st_ref[0, 0] = h_new.astype(st_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, chunk=128, interpret=None):
    """SSD over one sequence.

    x: (B, S, H, P); dt: (B, S, H); A: (H,); Bm/Cm: (B, S, N).
    Returns (y: (B, S, H, P), state: (B, H, N, P)).  S % chunk == 0.
    """
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    L = chunk
    nc = S // L
    xt = x.transpose(0, 2, 1, 3)                   # (B, H, S, P)
    dtt = dt.transpose(0, 2, 1)                    # (B, H, S)

    kernel = functools.partial(_kernel, nc=nc, L=L)
    y, st = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, L, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, L), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, L, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, L, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ssd_scan",
    )(xt, dtt, A, Bm, Cm)
    return y.transpose(0, 2, 1, 3), st
