"""Fused RMSNorm Pallas kernel: one HBM read, fp32 statistics in-register.

Grid over row blocks; each block computes mean-square and the scaled output
in a single VMEM residency (XLA emits separate reduce + mul passes on CPU;
on TPU this saves one full activation round-trip)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, g_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)             # (R, D)
    g = g_ref[...].astype(jnp.float32)             # (1, D)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * (1.0 + g)
    o_ref[...] = y.astype(o_ref.dtype)


def rmsnorm(x: jax.Array, gain: jax.Array, *, eps: float = 1e-6,
            block_rows: int = 128, interpret: bool) -> jax.Array:
    """x: (..., D); gain: (D,).  (1+gain) parameterization (see layers)."""
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = 1
    for s in orig_shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    block_rows = min(block_rows, rows)
    while rows % block_rows:
        block_rows //= 2
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
    )(x2, gain.reshape(1, d))
    return out.reshape(orig_shape)
