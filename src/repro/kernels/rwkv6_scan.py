"""Pallas TPU kernel for the RWKV-6 chunked WKV recurrence.

Grid: (batch, heads, num_chunks) with the chunk dimension sequential; the
(hd x hd) fp32 recurrent state lives in VMEM scratch, carried across chunk
iterations (initialized at chunk 0, written out at the last chunk).  Within
a chunk the math matches models/rwkv6.wkv_chunked: intra-chunk pairwise
decay attention + inter-chunk state contribution, all on (C x hd) tiles so
the pairwise (C x C) products run on the MXU.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CLAMP = -30.0


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, sout_ref, s_scr,
                *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0, 0].astype(jnp.float32)          # (C, hd)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)             # (1, hd)

    csum = jnp.cumsum(lw, axis=0)
    total = csum[-1:]
    dec_in = jnp.exp(jnp.maximum(csum - lw, CLAMP))
    dec_out = jnp.exp(jnp.maximum(total - csum, CLAMP))

    state = s_scr[...]                           # (hd, hd)
    o_inter = jax.lax.dot_general(r * dec_in, state, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    rd = r * dec_in
    kd = k * jnp.exp(jnp.clip(-csum, CLAMP, -CLAMP))
    att = jax.lax.dot_general(rd, kd, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (C, C)
    c = att.shape[0]
    ii = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    att = jnp.where(jj < ii, att, 0.0)
    diag = jnp.sum(r * k * u, axis=1)            # (C,)
    att = att + jnp.where(jj == ii, diag[:, None], 0.0)
    o_intra = jax.lax.dot_general(att, v, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    kdec = k * dec_out
    s_new = state * jnp.exp(jnp.maximum(total, 2 * CLAMP))[0][:, None] + \
        jax.lax.dot_general(kdec, v, (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s_scr[...] = s_new
    o_ref[0, 0] = (o_inter + o_intra).astype(o_ref.dtype)

    @pl.when(ci == nc - 1)
    def _fin():
        sout_ref[0, 0] = s_new.astype(sout_ref.dtype)


def rwkv6_wkv(r, k, v, logw, u, *, chunk: int = 128, interpret: bool
              ) -> Tuple[jax.Array, jax.Array]:
    """r,k,v,logw: (B, H, S, hd); u: (H, hd).

    Returns (o (B,H,S,hd) fp32, final_state (B,H,hd,hd) fp32).
    Zero initial state (use the jnp path for chained segments)."""
    b, h, s, hd = r.shape
    chunk = min(chunk, s)
    nc = s // chunk
    kernel = functools.partial(_wkv_kernel, chunk=chunk)
    o, sout = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, hd), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, chunk, hd), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, chunk, hd), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, chunk, hd), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, hd), lambda b_, h_, c_: (h_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, hd), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b_, h_, c_: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, h, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(r, k, v, logw, u)
    return o, sout
