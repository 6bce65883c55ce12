"""Pallas TPU paged-attention kernel (decode over a paged KV pool).

The serving engine stores KV in fixed-size pages granted by the sizing LP
(serving/kv_cache.py); decode must attend over each request's page list.
TPU-native design: the page table is a *scalar-prefetch* operand --
``pltpu.PrefetchScalarGridSpec`` hands it to the BlockSpec index maps, so
the pipeline DMAs exactly the pages named by the table (no gather of the
whole pool).  Grid: (batch, kv_heads, max_pages) with the page dimension
sequential; online-softmax state for the grouped queries lives in VMEM
scratch.  Out-of-range pages (table entry < 0) are skipped via pl.when --
requests shorter than max_pages cost only their own pages' DMAs.

Sliding-window (ATTN_LOCAL) layers run the same kernel with
``window > 0``: only keys at positions ``(pos - window, pos]`` score.
With ``ring=True`` the page table is a fixed *ring* of
``ceil(window/PAGE_SIZE)+1`` pages -- token position ``p`` lives at ring
slot ``p % (max_pages * page_size)``, so a slot's absolute position is
recovered as the latest ``p' <= pos`` congruent to the slot index
(modulo the ring size), exactly mirroring the dense ring cache in
``models/attention.self_attention_decode``.

Prefix-cache interaction (serving/prefix_cache.py): a request's page
table may MIX two id classes -- leading entries that are cache-owned
PHYSICAL page ids (refcounted, read-only prefix pages shared across
requests and tenants) followed by view-translated private ids.  The
kernel is oblivious: both classes index the same pool-sized arrays, and
decode only ever *writes* the private tail (the write position ``p``
satisfies ``p // page_size >= len(shared_pages)``), so shared pages are
strictly read-only here.  Nothing in the kernel changes; this note
exists because the table is no longer uniformly view-local.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _slot_positions(slot, last, *, window: int, ring: bool, ring_tokens: int):
    """(abs position, valid?) of ring/linear cache slots given the last
    written position ``last`` (= valid_len - 1).

    Linear tables store position ``s`` at slot ``s``.  Ring tables store
    position ``p`` at slot ``p % ring_tokens``; the slot's current
    occupant is the LATEST ``p' <= last`` congruent to the slot index,
    i.e. ``last - ((last - s) % ring_tokens)`` (negative -> never
    written).  ``window > 0`` additionally masks positions at or below
    ``last - window``."""
    if ring:
        pos = last - jnp.remainder(last - slot, ring_tokens)
    else:
        pos = slot
    ok = (pos >= 0) & (pos <= last)
    if window > 0:
        ok = ok & (pos > last - window)
    return pos, ok


def _paged_kernel(table_ref, q_ref, k_ref, v_ref, len_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, page_size: int, scale: float,
                  window: int, ring: bool):
    b, h, pi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    np_ = pl.num_programs(2)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    page_id = table_ref[b, pi]
    valid_len = len_ref[b]
    s_start = pi * page_size
    # a ring page can hold live tokens regardless of its table index, so
    # the start-beyond-length early-exit only applies to linear tables
    live = (page_id >= 0) if ring else (page_id >= 0) & (s_start < valid_len)

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)          # (G, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (page, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        slot = s_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _, ok = _slot_positions(slot, valid_len - 1, window=window,
                                ring=ring, ring_tokens=np_ * page_size)
        s = jnp.where(ok, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(pi == np_ - 1)
    def _fin():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    page_table: jax.Array, valid_len: jax.Array, *,
                    window: int = 0, ring: bool = False,
                    interpret: bool) -> jax.Array:
    """q: (B, H, D); k/v_pages: (P, page, KV, D) pool; page_table:
    (B, max_pages) int32 (-1 padded); valid_len: (B,) total tokens.

    Table entries are PHYSICAL page ids: the pool arrays may be a
    pod-shared :class:`~repro.serving.model_runner.KVArrayStore` aliased
    by several tenants, and only physical ids are unique across it --
    callers translate view-local ids (``PoolView.to_physical``) before
    building the table.

    ``window > 0`` masks keys outside the last ``window`` positions;
    ``ring=True`` additionally treats the table as a position-modular
    ring of ``max_pages`` pages (sliding-window layers' bounded tables).

    Returns (B, H, D)."""
    b, h, d = q.shape
    pool, page, kvh, _ = k_pages.shape
    g = h // kvh
    max_pages = page_table.shape[1]
    qg = q.reshape(b, kvh, g, d)
    # pool laid out (KV, P, page, d) so a block is one head's one page
    kp = k_pages.transpose(2, 0, 1, 3)
    vp = v_pages.transpose(2, 0, 1, 3)
    vlen = jnp.broadcast_to(jnp.asarray(valid_len, jnp.int32), (b,))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kvh, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, h_, p_, tbl: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, page, d),
                         lambda b_, h_, p_, tbl: (h_, jnp.maximum(
                             tbl[b_, p_], 0), 0, 0)),
            pl.BlockSpec((1, 1, page, d),
                         lambda b_, h_, p_, tbl: (h_, jnp.maximum(
                             tbl[b_, p_], 0), 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda b_, h_, p_, tbl: (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, page_size=page,
                          scale=d ** -0.5, window=window, ring=ring),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), qg, kp, vp, vlen)
    return out.reshape(b, h, d)


def paged_attention_ref(q, k_pages, v_pages, page_table, valid_len, *,
                        window: int = 0, ring: bool = False):
    """Gather-based jnp oracle (same window/ring semantics as the
    kernel)."""
    b, h, d = q.shape
    pool, page, kvh, _ = k_pages.shape
    max_pages = page_table.shape[1]
    safe = jnp.maximum(page_table, 0)                        # (B, MP)
    k = k_pages[safe]                                        # (B, MP, page, KV, d)
    v = v_pages[safe]
    k = k.reshape(b, max_pages * page, kvh, d)
    v = v.reshape(b, max_pages * page, kvh, d)
    k = jnp.repeat(k, h // kvh, axis=2)
    v = jnp.repeat(v, h // kvh, axis=2)
    scores = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * (d ** -0.5)
    vlen = jnp.broadcast_to(jnp.asarray(valid_len), (b,))
    slot = jnp.arange(max_pages * page)[None, None, :]
    in_page = (jnp.repeat(page_table >= 0, page, axis=1))[:, None, :]
    _, ok = _slot_positions(slot, vlen[:, None, None] - 1, window=window,
                            ring=ring, ring_tokens=max_pages * page)
    mask = ok & in_page
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(mask, probs, 0.0)   # fully-masked rows stay finite
    return jnp.einsum("bhs,bshd->bhd", probs,
                      v.astype(jnp.float32)).astype(q.dtype)
