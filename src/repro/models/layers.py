"""Shared building blocks: norms, rotary embeddings, MLPs, embeddings.

Pure-function style: every layer is ``f(params, x, ...) -> y`` over plain
pytrees.  Parameter *specs* (shape/dtype/logical axes) live next to the
``init``/``apply`` pair so that the resource-graph profiles and the sharding
planner share one source of truth with the compute code.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]

# ---------------------------------------------------------------------------
# Param-spec helper: a leaf spec is (shape, logical_axes, init_scale)
# ---------------------------------------------------------------------------


class Spec:
    """Parameter leaf spec: shape + logical axis names + init std."""

    __slots__ = ("shape", "axes", "std")

    def __init__(self, shape: Tuple[int, ...], axes: Tuple[Optional[str], ...],
                 std: float = 0.02):
        assert len(shape) == len(axes), (shape, axes)
        self.shape = tuple(shape)
        self.axes = tuple(axes)
        self.std = std

    def __repr__(self):
        return f"Spec{self.shape}{self.axes}"


def is_spec(x) -> bool:
    return isinstance(x, Spec)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal_leaf(key: jax.Array, shape: Tuple[int, ...], std: float,
                 dtype) -> jax.Array:
    """One weight drawn in f32, scaled and cast in ONE program, so the f32
    draw stays inside the fusion.  Drawn op by op, each stacked weight
    held its f32 draw and its scaled copy in device memory, and dispatch
    running ahead kept several of them in flight at once."""
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_from_specs(rng: jax.Array, specs, dtype=jnp.bfloat16):
    """Materialize a params pytree from a spec pytree."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(rng, len(leaves))
    out = []
    for key, spec in zip(keys, leaves):
        if spec.std == 0.0:  # zeros (biases, some gates)
            out.append(jnp.zeros(spec.shape, dtype))
        elif spec.std == 1.0 and len(spec.shape) <= 2 and (
                len(spec.shape) == 1 or spec.shape[-1] == spec.shape[0]):
            # norm gains default to ones
            out.append(jnp.ones(spec.shape, dtype))
        else:
            out.append(_normal_leaf(key, spec.shape, float(spec.std),
                                    jnp.dtype(dtype)))
    return jax.tree.unflatten(treedef, out)


def shape_structs(specs, dtype=jnp.bfloat16):
    """Spec tree -> ShapeDtypeStruct tree (for dry-run lowering)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype), specs,
        is_leaf=is_spec)


def logical_axes(specs):
    """Spec tree -> logical-axes tree (tuples of axis names)."""
    return jax.tree.map(lambda s: s.axes, specs, is_leaf=is_spec)


def param_bytes(specs, bytes_per_param: int = 2) -> int:
    total = 0
    for s in jax.tree.leaves(specs, is_leaf=is_spec):
        total += int(np.prod(s.shape)) * bytes_per_param
    return total


def param_count(specs) -> int:
    return sum(int(np.prod(s.shape))
               for s in jax.tree.leaves(specs, is_leaf=is_spec))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, gain: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + gain.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x: jax.Array, gain: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * gain.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def group_norm_heads(x: jax.Array, gain: jax.Array, eps: float = 64e-5):
    """Per-head group norm over the last dim of (..., H, hd) (rwkv6 style)."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def rms_norm_spec(d: int) -> Spec:
    return Spec((d,), ("embed",), std=0.0)  # zero-init: (1+g) parameterization


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    if theta <= 0:
        return x
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                      # (hd/2,)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(ang)[..., None, :]                   # (..., S, 1, hd/2)
    sin = jnp.sin(ang)[..., None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : hd // 2], x32[..., hd // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq_len: int, d_model: int) -> jax.Array:
    pos = np.arange(seq_len)[:, None]
    dim = np.arange(0, d_model, 2)[None, :]
    ang = pos / np.power(10_000.0, dim / d_model)
    out = np.zeros((seq_len, d_model), np.float32)
    out[:, 0::2] = np.sin(ang)
    out[:, 1::2] = np.cos(ang)
    return jnp.asarray(out)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def gated_mlp_specs(d_model: int, d_ff: int) -> Params:
    return {
        "wi_gate": Spec((d_model, d_ff), ("embed", "ffn")),
        "wi_up": Spec((d_model, d_ff), ("embed", "ffn")),
        "wo": Spec((d_ff, d_model), ("ffn", "embed")),
    }


def gated_mlp(p: Params, x: jax.Array, act=jax.nn.silu) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, p["wi_gate"])
    u = jnp.einsum("...d,df->...f", x, p["wi_up"])
    return jnp.einsum("...f,fd->...d", act(g) * u, p["wo"])


def mlp_specs(d_model: int, d_ff: int) -> Params:
    return {
        "wi": Spec((d_model, d_ff), ("embed", "ffn")),
        "bi": Spec((d_ff,), ("ffn",), std=0.0),
        "wo": Spec((d_ff, d_model), ("ffn", "embed")),
        "bo": Spec((d_model,), ("embed",), std=0.0),
    }


def mlp(p: Params, x: jax.Array, act=jax.nn.gelu) -> jax.Array:
    h = act(jnp.einsum("...d,df->...f", x, p["wi"]) + p["bi"])
    return jnp.einsum("...f,fd->...d", h, p["wo"]) + p["bo"]


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embed_specs(vocab: int, d_model: int, tie: bool) -> Params:
    out = {"tok": Spec((vocab, d_model), ("vocab", "embed"))}
    if not tie:
        out["head"] = Spec((d_model, vocab), ("embed", "vocab"))
    return out


def embed(p: Params, tokens: jax.Array, scale: float = 1.0) -> jax.Array:
    x = jnp.take(p["tok"], tokens, axis=0)
    if scale != 1.0:
        x = (x.astype(jnp.float32) * scale).astype(x.dtype)
    return x


def unembed(p: Params, x: jax.Array, softcap: float = 0.0) -> jax.Array:
    if "head" in p:
        logits = jnp.einsum("...d,dv->...v", x, p["head"])
    else:
        logits = jnp.einsum("...d,vd->...v", x, p["tok"])
    logits = logits.astype(jnp.float32)
    if softcap > 0:
        logits = jnp.tanh(logits / softcap) * softcap
    return logits


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array,
                          mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean CE over valid positions.  logits fp32 (..., V); labels (...).

    SPMD note: the label log-prob is extracted with a one-hot contraction,
    NOT take_along_axis -- a vocab-dim gather on vocab-sharded logits makes
    the partitioner replicate the full logits per device (measured
    ~290 GiB/device on command-r train_4k); the contraction partitions
    cleanly into a partial sum + tiny all-reduce."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    ll = jnp.einsum("...v,...v->...", logits, onehot)
    nll = lse - ll
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
