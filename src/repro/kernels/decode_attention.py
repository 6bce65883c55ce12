"""Pallas TPU decode-attention kernel (single-token query, long KV).

Decode is memory-bound: the whole KV cache streams HBM->VMEM once while
queries stay resident.  Grid: (batch, kv_heads, seq_blocks) with the seq
dimension sequential; the per-(batch, kv-head) online-softmax state for all
``group`` grouped queries is VMEM scratch.  GQA stays folded (the q block
carries the whole group for one KV head), so arithmetic intensity per KV
byte is maximized -- the TPU analog of flash-decoding's split-K, with the
cross-shard combine handled at the SPMD level (models/attention
seqshard path) rather than inside the kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, len_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, block_s: int, scale: float):
    si = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    valid_len = len_ref[0]
    s_start = si * block_s

    @pl.when(s_start < valid_len)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)          # (G, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bs, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = s_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < valid_len, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(pos < valid_len, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(si == ns - 1)
    def _fin():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     valid_len: jax.Array, *, block_s: int = 512,
                     interpret: bool) -> jax.Array:
    """q: (B, H, D); k, v: (B, KVH, S, D); valid_len: () or (B,) int32.

    Returns (B, H, D).  Attends over positions [0, valid_len)."""
    b, h, d = q.shape
    kvh, s = k.shape[1], k.shape[2]
    g = h // kvh
    block_s = min(block_s, s)
    ns = s // block_s
    qg = q.reshape(b, kvh, g, d)
    vlen = jnp.broadcast_to(jnp.asarray(valid_len, jnp.int32), (b,))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_s=block_s, scale=d ** -0.5),
        grid=(b, kvh, ns),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, h_, s_: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, block_s, d),
                         lambda b_, h_, s_: (b_, h_, s_, 0)),
            pl.BlockSpec((1, 1, block_s, d),
                         lambda b_, h_, s_: (b_, h_, s_, 0)),
            pl.BlockSpec((1,), lambda b_, h_, s_: (b_,)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b_, h_, s_: (b_, h_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        interpret=interpret,
    )(qg, k, v, vlen)
    return out.reshape(b, h, d)
