"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

Grid: (batch, heads, num_chunks), chunk dimension sequential; the (P x N)
fp32 SSM state sits in VMEM scratch.  Per chunk: the (C x C) decay-masked
``C B^T`` product runs on the MXU; the inter-chunk term contracts the
carried state with C_t.  Matches models/mamba2.ssd_chunked (the oracle is
ref.ssd_ref / the per-step recurrence)."""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CLAMP = -30.0


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, sout_ref, s_scr,
                *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    x = x_ref[0, 0].astype(jnp.float32)        # (C, P)
    dt = dt_ref[0, 0].astype(jnp.float32)      # (C, 1)
    a = a_ref[0, 0].astype(jnp.float32)        # (C, 1)
    bmat = b_ref[0].astype(jnp.float32)        # (C, N)
    cmat = c_ref[0].astype(jnp.float32)        # (C, N)

    csum = jnp.cumsum(a, axis=0)               # (C, 1) inclusive
    total = csum[-1:]
    dec_in = jnp.exp(jnp.maximum(csum, CLAMP))
    dec_out = jnp.exp(jnp.maximum(total - csum, CLAMP))

    state = s_scr[...]                          # (P, N)
    y_inter = jax.lax.dot_general(cmat, state, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_inter = y_inter * dec_in                  # (C, P)

    att = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (C, C)
    c = att.shape[0]
    ii = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    pair = jnp.exp(jnp.clip(csum - csum[:, 0][None, :], CLAMP, -CLAMP))
    w = jnp.where(jj <= ii, att * pair, 0.0)    # (C, C)
    y_intra = jax.lax.dot_general(w, x * dt, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    kdec = bmat * (dt * dec_out)                # (C, N)
    s_new = state * jnp.exp(jnp.maximum(total, 2 * CLAMP))[0] + \
        jax.lax.dot_general(x, kdec, (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (P, N)
    s_scr[...] = s_new
    y_ref[0, 0] = (y_inter + y_intra).astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _fin():
        sout_ref[0, 0] = s_new.astype(sout_ref.dtype)


def ssd_scan(x, dt, a, b, c, *, chunk: int = 128, interpret: bool
             ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, H, S, P); dt, a: (B, H, S); b, c: (B, S, N).

    Returns (y (B,H,S,P) fp32, final state (B,H,P,N) fp32)."""
    bsz, h, s, p_dim = x.shape
    n = b.shape[-1]
    chunk = min(chunk, s)
    nc = s // chunk
    dt3 = dt[..., None]
    a3 = a[..., None]
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, sout = pl.pallas_call(
        kernel,
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p_dim),
                         lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, c_: (b_, c_, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, c_: (b_, c_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p_dim),
                         lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, p_dim, n), lambda b_, h_, c_: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, s, p_dim), jnp.float32),
            jax.ShapeDtypeStruct((bsz, h, p_dim, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p_dim, n), jnp.float32)],
        interpret=interpret,
    )(x, dt3, a3, b, c)
    return y, sout
