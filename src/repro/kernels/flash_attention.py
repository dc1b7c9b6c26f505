"""Blocked online-softmax (flash) attention — Pallas TPU kernel.

Grid: (batch, heads, q_blocks, kv_blocks); the kv dimension is innermost
and sequential ("arbitrary"), carrying the running max / denominator /
accumulator in VMEM scratch across kv blocks of one (b, h, iq) tile.

TPU adaptation notes (vs the CUDA flash-attention the literature targets):
  * block shapes are MXU-aligned (q, kv blocks multiples of 128 on the
    sequence axes; head_dim padded to 128 by the wrapper when needed);
  * no shared-memory banking / warp shuffles — the VMEM scratch + the
    sequential grid dimension express the same reduction;
  * causal + local-window masking is positional; fully-masked kv blocks
    are skipped with pl.when (block-sparse skip on the causal lower
    triangle), which roughly halves causal FLOPs.

GQA: the wrapper maps query head h to kv head h // (H / KV) in the
BlockSpec index map — no kv replication in HBM.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, causal, window, bq, bk, nk):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * bq
    k_start = ik * bk
    # block-level skip: causal => no kv block strictly above the diagonal;
    # window => no kv block entirely left of the window
    needed = True
    if causal:
        needed = k_start <= q_start + bq - 1
    if window:
        needed = needed & (k_start + bk - 1 > q_start - window)

    @pl.when(needed)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                     # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        logits = jnp.where(mask, logits, NEG_INF)
        m_prev = m_ref[...]                          # (bq, 1)
        m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
        p = jnp.exp(logits - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "bq", "bk", "interpret"),
)
def flash_attention(
    q, k, v, causal=True, window=0, bq=128, bk=128, interpret=None
):
    """q: (B, H, S, D); k, v: (B, KV, T, D); returns (B, H, S, D).

    Self-attention with positions == arange (train/prefill).  S, T must be
    multiples of the block sizes (the ops wrapper pads).
    """
    B, H, S, D = q.shape
    KV, T = k.shape[1], k.shape[2]
    rep = H // KV
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    nq, nk = S // bq, T // bk
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, nk=nk,
    )
    grid = (B, H, nq, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h // rep, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h // rep, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
