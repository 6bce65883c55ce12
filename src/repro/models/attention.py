"""Attention: GQA self/cross attention for train, prefill and decode.

Three execution strategies (the materializer picks per invocation class,
mirroring the paper's local-vs-remote compilation versions):

* ``naive``   -- full (S x S) score materialization.  Cheapest HLO for short
                 sequences; O(S^2) activation memory.
* ``chunked`` -- online-softmax scan over query chunks (flash-attention
                 algorithm in pure jnp).  O(S * chunk) activation memory;
                 the jnp oracle for the Pallas flash kernel.
* Pallas flash kernel (kernels/flash_attention.py) -- TPU target; dispatched
  via kernels/ops.py when enabled.

Decode uses a KV cache: full-length for global attention, ring buffer of
window size for sliding-window attention (bounds gemma3's long_500k KV).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import Spec, apply_rope, rms_norm, rms_norm_spec

Params = Dict[str, Any]

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------

def attn_specs(cfg: ModelConfig, cross: bool = False) -> Params:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    std = 0.02
    p = {
        "wq": Spec((d, h, hd), ("embed", "q_heads", "head_dim"), std),
        "wk": Spec((d, kv, hd), ("embed", "kv_heads", "head_dim"), std),
        "wv": Spec((d, kv, hd), ("embed", "kv_heads", "head_dim"), std),
        "wo": Spec((h, hd, d), ("q_heads", "head_dim", "embed"), std),
    }
    if cfg.use_qk_norm:
        p["q_norm"] = rms_norm_spec(hd)
        p["k_norm"] = rms_norm_spec(hd)
    if cross:
        p = {f"self_{k}": v for k, v in p.items()}
        p.update({
            "cross_wq": Spec((d, h, hd), ("embed", "q_heads", "head_dim"), std),
            "cross_wk": Spec((d, kv, hd), ("embed", "kv_heads", "head_dim"), std),
            "cross_wv": Spec((d, kv, hd), ("embed", "kv_heads", "head_dim"), std),
            "cross_wo": Spec((h, hd, d), ("q_heads", "head_dim", "embed"), std),
        })
    return p


# ---------------------------------------------------------------------------
# Core scaled-dot-product attention (shared by all modes)
# ---------------------------------------------------------------------------

def _expand_kv(k: jax.Array, num_heads: int) -> jax.Array:
    """(B, S, KV, hd) -> (B, S, H, hd) by repeating each KV head."""
    kvh = k.shape[-2]
    if kvh == num_heads:
        return k
    return jnp.repeat(k, num_heads // kvh, axis=-2)


def _mask_bias(q_pos: jax.Array, k_pos: jax.Array, causal: bool,
               window: int, k_valid: Optional[jax.Array]) -> jax.Array:
    """Additive fp32 bias (..., Sq, Sk) built from position tensors."""
    ok = jnp.ones((q_pos.shape[-1], k_pos.shape[-1]), bool)
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    if causal:
        ok = ok & (kp <= qp)
    if window > 0:
        ok = ok & (kp > qp - window)
    if k_valid is not None:
        ok = ok & k_valid[..., None, :]
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def sdpa(q: jax.Array, k: jax.Array, v: jax.Array, *,
         causal: bool, window: int = 0,
         q_positions: Optional[jax.Array] = None,
         k_positions: Optional[jax.Array] = None,
         k_valid: Optional[jax.Array] = None,
         impl: str = "naive", chunk: int = 1024) -> jax.Array:
    """q: (B, Sq, H, hd); k, v: (B, Sk, KV, hd) -> (B, Sq, H, hd)."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    k = _expand_kv(k, h)
    v = _expand_kv(v, h)
    if q_positions is None:
        q_positions = jnp.arange(sq)
    if k_positions is None:
        k_positions = jnp.arange(sk)
    scale = hd ** -0.5

    if (impl == "banded" and causal and window > 0 and k_valid is None
            and sq == sk and sq % chunk == 0 and sq > chunk
            and window <= chunk):
        # opt-in (see EXPERIMENTS §Perf): 2.4x lower compute/memory TERMS on
        # gemma3 train but +12 GiB adjusted peak from band-tile residency
        # under remat -- the fused Pallas flash kernel (window tiles skipped
        # via _tile_live) is the form that gets the FLOP win without the
        # residency cost on real TPUs.
        return _banded_sdpa(q, k, v, window=window, chunk=chunk, scale=scale)

    if impl == "chunked" and sq > chunk and sq % chunk == 0:
        # (indivisible short sequences -- e.g. whisper's 1500-frame
        # encoder -- fall through to the naive path)
        return _chunked_sdpa(q, k, v, causal=causal, window=window,
                             q_positions=q_positions, k_positions=k_positions,
                             k_valid=k_valid, chunk=chunk, scale=scale)

    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    bias = _mask_bias(q_positions, k_positions, causal, window, k_valid)
    scores = scores + bias[..., None, :, :] if bias.ndim == 2 else scores + bias
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _banded_sdpa(q, k, v, *, window, chunk, scale):
    """Causal sliding-window attention over uniform key bands.

    For query chunk starting at q0, only keys [q0 - window, q0 + chunk)
    can be unmasked.  K/V are left-padded by `window` so every band has
    uniform width (chunk + window) at stride chunk, letting a remat'd
    lax.scan stream one band at a time: score FLOPs/bytes drop from
    O(S^2) to O(S * (chunk + window)) and only one band tile is resident.
    Requires window <= chunk (gemma3: 1024 <= 1024)."""
    b, s, h, hd = q.shape
    n = s // chunk
    kw = chunk + window
    kp = jnp.pad(k, ((0, 0), (window, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (window, 0), (0, 0), (0, 0)))
    qc = q.reshape(b, n, chunk, h, hd).transpose(1, 0, 2, 3, 4)

    def body(_, inp):
        i, qi = inp
        q0 = i * chunk
        ki = jax.lax.dynamic_slice_in_dim(kp, q0, kw, axis=1)
        vi = jax.lax.dynamic_slice_in_dim(vp, q0, kw, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qi, ki).astype(jnp.float32)
        scores = scores * scale
        qpos = q0 + jnp.arange(chunk)[:, None]
        kpos = q0 + jnp.arange(kw)[None, :] - window   # absolute key pos
        ok = (kpos >= 0) & (kpos <= qpos) & (kpos > qpos - window)
        scores = jnp.where(ok, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(qi.dtype)
        return None, jnp.einsum("bhqk,bkhd->bqhd", probs, vi)

    _, out = jax.lax.scan(jax.remat(body), None,
                          (jnp.arange(n), qc))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, hd)


def _chunked_sdpa(q, k, v, *, causal, window, q_positions, k_positions,
                  k_valid, chunk, scale):
    """Online-softmax over query chunks; O(Sq/chunk) scan with remat body.

    Memory: O(B * H * chunk * Sk) score tile per iteration instead of the
    full (Sq x Sk).  This is the flash-attention recurrence and serves as
    the jnp oracle for the Pallas kernel.
    """
    b, sq, h, hd = q.shape
    nq = sq // chunk
    assert sq % chunk == 0, (sq, chunk)
    qc = q.reshape(b, nq, chunk, h, hd).transpose(1, 0, 2, 3, 4)
    qp = q_positions.reshape(nq, chunk)

    def body(_, inputs):
        qi, qpi = inputs
        scores = jnp.einsum("bqhd,bkhd->bhqk", qi, k).astype(jnp.float32)
        scores = scores * scale
        bias = _mask_bias(qpi, k_positions, causal, window, k_valid)
        scores = scores + bias
        m = jnp.max(scores, axis=-1, keepdims=True)
        m = jnp.maximum(m, NEG_INF)  # guard fully-masked rows
        p = jnp.exp(scores - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(qi.dtype), v)
        o = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1, 3).astype(o.dtype)
        return None, o

    _, out = jax.lax.scan(jax.remat(body), None, (qc, qp))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, hd)


# ---------------------------------------------------------------------------
# Self attention block application (train / prefill / decode)
# ---------------------------------------------------------------------------

def project_qkv(p: Params, x: jax.Array, cfg: ModelConfig,
                positions: jax.Array, prefix: str = "") -> Tuple[jax.Array, ...]:
    q = jnp.einsum("bsd,dnh->bsnh", x, p[prefix + "wq"])
    k = jnp.einsum("bsd,dnh->bsnh", x, p[prefix + "wk"])
    v = jnp.einsum("bsd,dnh->bsnh", x, p[prefix + "wv"])
    if cfg.use_qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_out(p: Params, o: jax.Array, prefix: str = "") -> jax.Array:
    return jnp.einsum("bsnh,nhd->bsd", o, p[prefix + "wo"])


def self_attention_train(p: Params, x: jax.Array, cfg: ModelConfig, *,
                         causal: bool = True, window: int = 0,
                         impl: str = "naive", chunk: int = 1024,
                         positions: Optional[jax.Array] = None,
                         prefix: str = "") -> jax.Array:
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)
    q, k, v = project_qkv(p, x, cfg, positions, prefix)
    o = sdpa(q, k, v, causal=causal, window=window, impl=impl, chunk=chunk,
             q_positions=positions, k_positions=positions)
    return attn_out(p, o, prefix)


# ---------------------------------------------------------------------------
# Sequence-sharded decode ("flash-decode" adaptation)
#
# When KV heads don't divide the model axis (GQA kv=8 on a 16-wide axis) the
# materializer shards the KV cache along the *sequence* dim instead.  Two
# SPMD hazards must be avoided: (a) dynamic_update_slice into a sharded dim
# makes the partitioner gather the whole cache; (b) jnp.repeat-style GQA
# expansion reshapes the sharded operand.  ``seqshard_cache_update`` does a
# local, comm-free single-row write under shard_map, and the decode SDPA
# below keeps KV in (S, KV, hd) form, contracting with grouped queries so
# the only collectives are the tiny partial-softmax combines.
# ---------------------------------------------------------------------------

def seqshard_cache_update(cache: jax.Array, new: jax.Array, slot: jax.Array,
                          mesh, seq_axes: Tuple[str, ...],
                          batch_axes: Tuple[str, ...]) -> jax.Array:
    """Write one token row into a sequence-sharded KV cache, locally.

    cache: (B, KV, S, hd) sharded on S over ``seq_axes``; new: (B, KV, 1,
    hd); slot: scalar global row.  Only the owning shard writes."""
    from jax.sharding import PartitionSpec as P

    bspec = (batch_axes if len(batch_axes) > 1 else
             (batch_axes[0] if batch_axes else None))
    sspec = seq_axes if len(seq_axes) > 1 else seq_axes[0]
    cache_spec = P(bspec, None, sspec, None)
    new_spec = P(bspec, None, None, None)

    def local(cache_l, new_l, slot_):
        s_loc = cache_l.shape[2]
        lin = jnp.zeros((), jnp.int32)
        for ax in seq_axes:
            lin = lin * mesh.shape[ax] + jax.lax.axis_index(ax)
        off = lin * s_loc
        loc = jnp.clip(slot_ - off, 0, s_loc - 1)
        in_range = (slot_ >= off) & (slot_ < off + s_loc)
        cur = jax.lax.dynamic_slice_in_dim(cache_l, loc, 1, 2)
        val = jnp.where(in_range, new_l.astype(cache_l.dtype), cur)
        return jax.lax.dynamic_update_slice_in_dim(cache_l, val, loc, 2)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(cache_spec, new_spec, P()),
        out_specs=cache_spec, check_vma=False)(cache, new, slot)


def gqa_decode_sdpa(q: jax.Array, k: jax.Array, v: jax.Array,
                    k_valid: jax.Array) -> jax.Array:
    """Decode attention without expanding KV heads (seq-shard friendly).

    Layout note: the cache is stored (B, KV, S, hd) -- contraction dims are
    minor-most, so XLA needs no (hoistable, cache-sized) transposes inside
    the per-layer scan (measured: 0.35 GiB/layer of hoisted transpose
    buffers with the (B, S, KV, hd) layout on command-r decode_32k).

    q: (B, 1, H, hd); k, v: (B, KV, S, hd); k_valid: (S,) bool.
    Returns (B, 1, H, hd)."""
    b, one, h, hd = q.shape
    kv = k.shape[1]
    g = h // kv
    qg = q.reshape(b, one, kv, g, hd)
    scores = jnp.einsum("bqkgh,bksh->bkgqs", qg, k).astype(jnp.float32)
    scores = scores * (hd ** -0.5)
    scores = jnp.where(k_valid[None, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bksh->bqkgh", probs.astype(q.dtype), v)
    return out.reshape(b, one, h, hd)


def init_kv_cache(cfg: ModelConfig, batch: int, cache_len: int,
                  window: int = 0, dtype=jnp.bfloat16):
    """One layer's KV cache struct, laid out (B, KV, S, hd) (see
    gqa_decode_sdpa layout note).  Ring buffer when window > 0."""
    s = min(cache_len, window) if window > 0 else cache_len
    shape = (batch, cfg.num_kv_heads, s, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def kv_cache_specs(cfg: ModelConfig, batch: int, cache_len: int,
                   window: int = 0, dtype=jnp.bfloat16):
    s = min(cache_len, window) if window > 0 else cache_len
    shape = (batch, cfg.num_kv_heads, s, cfg.head_dim)
    return {"k": jax.ShapeDtypeStruct(shape, dtype),
            "v": jax.ShapeDtypeStruct(shape, dtype)}


def self_attention_decode(p: Params, x: jax.Array, cache: Params,
                          pos: jax.Array, cfg: ModelConfig, *,
                          window: int = 0, prefix: str = "",
                          shard_ctx=None) -> Tuple[jax.Array, Params]:
    """One-token decode.  x: (B, 1, D); cache k/v: (B, S, KV, hd);
    pos: scalar current position.  Returns (out, new_cache).

    ``shard_ctx``: optional (mesh, seq_axes, batch_axes) when the cache is
    sequence-sharded (flash-decode materialization)."""
    s_cache = cache["k"].shape[2]
    positions = jnp.full((1,), pos, jnp.int32)
    q, k, v = project_qkv(p, x, cfg, positions, prefix)
    kt = k.transpose(0, 2, 1, 3)                    # (B, KV, 1, hd)
    vt = v.transpose(0, 2, 1, 3)

    slot = jnp.where(window > 0, pos % jnp.maximum(s_cache, 1), pos)
    if shard_ctx is not None:
        mesh, seq_axes, batch_axes = shard_ctx
        new_k = seqshard_cache_update(cache["k"], kt, slot, mesh, seq_axes,
                                      batch_axes)
        new_v = seqshard_cache_update(cache["v"], vt, slot, mesh, seq_axes,
                                      batch_axes)
    else:
        new_k = jax.lax.dynamic_update_slice_in_dim(cache["k"], kt, slot,
                                                    axis=2)
        new_v = jax.lax.dynamic_update_slice_in_dim(cache["v"], vt, slot,
                                                    axis=2)

    if window > 0:
        # ring buffer: slot i holds the largest absolute position p <= pos
        # with p % s_cache == i (i.e. the most recent write to that slot)
        idx = jnp.arange(s_cache)
        abs_pos = pos - ((pos - idx) % s_cache)
        k_valid = (abs_pos >= 0) & (abs_pos > pos - jnp.minimum(window, s_cache))
    else:
        idx = jnp.arange(s_cache)
        k_valid = idx <= pos

    o = gqa_decode_sdpa(q, new_k, new_v, k_valid)
    return attn_out(p, o, prefix), {"k": new_k, "v": new_v}


def self_attention_prefill(p: Params, x: jax.Array, cfg: ModelConfig, *,
                           window: int = 0, impl: str = "chunked",
                           chunk: int = 1024, cache_len: Optional[int] = None,
                           prefix: str = "") -> Tuple[jax.Array, Params]:
    """Full forward + returns populated KV cache (ring-sliced for SWA)."""
    b, s, _ = x.shape
    positions = jnp.arange(s)
    q, k, v = project_qkv(p, x, cfg, positions, prefix)
    o = sdpa(q, k, v, causal=True, window=window, impl=impl, chunk=chunk,
             q_positions=positions, k_positions=positions)
    if window > 0 and s > window:
        # keep the last `window` entries arranged by (abs_pos % window)
        tail_k, tail_v = k[:, -window:], v[:, -window:]
        shift = s % window
        cache = {"k": jnp.roll(tail_k, shift, axis=1),
                 "v": jnp.roll(tail_v, shift, axis=1)}
    else:
        cache = {"k": k, "v": v}
    cache = {n: a.transpose(0, 2, 1, 3) for n, a in cache.items()}
    return attn_out(p, o, prefix), cache


# ---------------------------------------------------------------------------
# Cross attention (whisper decoder)
# ---------------------------------------------------------------------------

def cross_attention(p: Params, x: jax.Array, enc_kv: Params,
                    cfg: ModelConfig) -> jax.Array:
    q = jnp.einsum("bsd,dnh->bsnh", x, p["cross_wq"])
    o = sdpa(q, enc_kv["k"], enc_kv["v"], causal=False, impl="naive")
    return attn_out(p, o, prefix="cross_")


def encode_cross_kv(p: Params, enc_out: jax.Array) -> Params:
    return {"k": jnp.einsum("btd,dnh->btnh", enc_out, p["cross_wk"]),
            "v": jnp.einsum("btd,dnh->btnh", enc_out, p["cross_wv"])}
