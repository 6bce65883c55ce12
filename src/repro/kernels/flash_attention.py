"""Pallas TPU flash-attention kernel (forward + backward).

TPU-native design (vs. a CUDA port):
  * Tiles are MXU/VMEM-shaped: (block_q x head_dim) / (block_k x head_dim)
    blocks staged HBM->VMEM by BlockSpecs; dot_generals hit the 128x128 MXU.
  * GQA is folded into the BlockSpec index maps (KV block index = q_head //
    group): no materialized head expansion in HBM.
  * Online-softmax running state (m, l, acc) lives in VMEM scratch and
    persists across the sequential k-block grid dimension.
  * Causal/sliding-window masks come from program ids; fully-masked tiles
    are skipped with pl.when (TPU analog of CUDA block skipping).

Backward is the standard two-pass flash recipe: recompute p from the saved
logsumexp; pass A accumulates dq over k-blocks, pass B accumulates (dk, dv)
over q-blocks.  ``flash_attention`` wires both through ``jax.custom_vjp``;
ref.py holds the jnp oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mask(block_q, block_k, q_start, k_start, causal, window):
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    m = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        m = m & (kpos <= qpos)
    if window > 0:
        m = m & (kpos > qpos - window)
    return m


def _tile_live(q_start, k_start, block_q, block_k, causal, window):
    """Whether any element of this (q, k) tile is unmasked."""
    live = jnp.asarray(True)
    if causal:
        live = live & (k_start <= q_start + block_q - 1)
    if window > 0:
        live = live & (k_start + block_k - 1 > q_start - window)
    return live


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, window, block_q, block_k):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    @pl.when(_tile_live(q_start, k_start, block_q, block_k, causal, window))
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _mask(block_q, block_k, q_start, k_start, causal, window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[...] + jnp.log(l))[:, 0]


def flash_attention_fwd(q, k, v, *, causal=True, window=0, block_q=128,
                        block_k=128, interpret: bool):
    """q: (B, H, S, D); k, v: (B, KVH, S, D) -> (o, lse (B,H,S) fp32)."""
    b, h, s, d = q.shape
    kvh = k.shape[1]
    g = h // kvh
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    nq, nk = s // block_q, s // block_k
    kernel = functools.partial(_fwd_kernel, scale=d ** -0.5, causal=causal,
                               window=window, block_q=block_q,
                               block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, q_, k_: (b_, h_, q_, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, q_, k_: (b_, h_ // g, k_, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, q_, k_: (b_, h_ // g, k_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, q_, k_: (b_, h_, q_, 0)),
            pl.BlockSpec((1, 1, block_q),
                         lambda b_, h_, q_, k_: (b_, h_, q_)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, scale, causal, window, block_q, block_k):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q_start, k_start = qi * block_q, ki * block_k

    @pl.when(_tile_live(q_start, k_start, block_q, block_k, causal, window))
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _mask(block_q, block_k, q_start, k_start, causal, window)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_scr[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _fin():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal, window,
                block_q, block_k):
    # grid: (b, kv_head, k_block, q_block, group)
    ki, qi, gi = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    nq, ng = pl.num_programs(3), pl.num_programs(4)

    @pl.when((qi == 0) & (gi == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_start, k_start = qi * block_q, ki * block_k

    @pl.when(_tile_live(q_start, k_start, block_q, block_k, causal, window))
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _mask(block_q, block_k, q_start, k_start, causal, window)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)          # (bq, bk)
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bk, d)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bk, d)

    @pl.when((qi == nq - 1) & (gi == ng - 1))
    def _fin():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, causal=True, window=0,
                        block_q=128, block_k=128, interpret: bool):
    b, h, s, d = q.shape
    kvh = k.shape[1]
    g = h // kvh
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    nq, nk = s // block_q, s // block_k
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                                  # (B, H, S)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=d ** -0.5, causal=causal,
                          window=window, block_q=block_q, block_k=block_k),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, q_, k_: (b_, h_, q_, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, q_, k_: (b_, h_ // g, k_, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, q_, k_: (b_, h_ // g, k_, 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, q_, k_: (b_, h_, q_, 0)),
            pl.BlockSpec((1, 1, block_q),
                         lambda b_, h_, q_, k_: (b_, h_, q_)),
            pl.BlockSpec((1, 1, block_q),
                         lambda b_, h_, q_, k_: (b_, h_, q_)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h_, q_, k_: (b_, h_, q_, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dk/dv: grid over kv heads; inner-most dims iterate q blocks x group
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=d ** -0.5, causal=causal,
                          window=window, block_q=block_q, block_k=block_k),
        grid=(b, kvh, nk, nq, g),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, kh, k_, q_, g_: (b_, kh * g + g_, q_, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, kh, k_, q_, g_: (b_, kh, k_, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, kh, k_, q_, g_: (b_, kh, k_, 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, kh, k_, q_, g_: (b_, kh * g + g_, q_, 0)),
            pl.BlockSpec((1, 1, block_q),
                         lambda b_, kh, k_, q_, g_: (b_, kh * g + g_, q_)),
            pl.BlockSpec((1, 1, block_q),
                         lambda b_, kh, k_, q_, g_: (b_, kh * g + g_, q_)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, kh, k_, q_, g_: (b_, kh, k_, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, kh, k_, q_, g_: (b_, kh, k_, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, kvh, s, d), k.dtype),
            jax.ShapeDtypeStruct((b, kvh, s, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom VJP: training differentiates through the kernel pair
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool, window: int, block_q: int,
                    block_k: int, interpret: bool):
    """q: (B, H, S, D); k, v: (B, KVH, S, D) -> (B, H, S, D)."""
    o, _ = flash_attention_fwd(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)
    return o


def _vjp_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    o, lse = flash_attention_fwd(q, k, v, causal=causal, window=window,
                                 block_q=block_q, block_k=block_k,
                                 interpret=interpret)
    return o, (q, k, v, o, lse)


def _vjp_bwd(causal, window, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    return flash_attention_bwd(q, k, v, o, lse, do, causal=causal,
                               window=window, block_q=block_q,
                               block_k=block_k, interpret=interpret)


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)
