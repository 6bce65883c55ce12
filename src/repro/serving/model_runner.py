"""Model execution backends for the serving engine.

The :class:`~repro.serving.engine.ServingEngine` owns admission, paging
and preemption; a :class:`ModelRunner` owns the device state and the two
model entry points the engine drives:

* ``prefill(req)``  -- full forward over the prompt, caching KV.
* ``decode(running)`` -- one batched greedy decode step.

Two implementations:

* :class:`DenseRunner` -- per-slot dense KV cache of ``cache_len`` tokens
  (the previous inline executor closure, extracted).  Decode attends over
  a contiguous cache via ``model.decode_step``; positions are shared
  across the batch (the historical approximation).
* :class:`PagedRunner` -- KV lives in the ``(pool_pages, PAGE_SIZE, KV,
  hd)`` layout granted page-by-page by the engine's pool; decode attends
  through :func:`repro.kernels.ops.paged_attention` (the compiled Pallas
  kernel on TPU, its jnp oracle elsewhere -- ``ops`` decides) driven by
  each request's page table.
  Positions and valid lengths are exact per request, so co-batched
  requests of different lengths decode correctly -- and the KV footprint
  is the pages the sizing policy granted, not ``max_batch * cache_len``.
  Mixed global/sliding-window stacks (gemma3-style) are supported:
  ATTN_LOCAL layers keep a fixed *ring* of ``ceil(window/PAGE_SIZE)+1``
  pages per request (see :class:`~repro.serving.kv_cache.PageGroups`)
  while global layers keep the growing table.  The device page arrays
  live in a :class:`KVArrayStore`; same-KV-shape tenants on one pod
  alias ONE store (physical sharing), with requests carrying view-local
  page ids remapped to physical ids at kernel time.

Compile discipline (long-run serving must not recompile per step):

* decode pads the batch to ``max_batch`` (idle lanes write into a trash
  page and are fully masked) and buckets the page-table width to the
  next power of two, so a bursty run triggers O(log pool) decode
  compiles, not O(steps);
* prefill scatters prompt KV page-by-page straight from a
  prompt-length-bucketed forward -- no dense ``n_pages * PAGE_SIZE``
  cache is ever built, so there is no per-grant-size recompile and no
  transient dense allocation.

Prompt tokens are synthesized from a *stable* digest of the request id
(``zlib.crc32``): ``hash()`` is salted per process, which made served
outputs nondeterministic across runs.
"""

from __future__ import annotations

import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import zensan
from repro.checkpoint.checkpointer import _from_saved, _to_savable
from repro.configs.base import ATTN_GLOBAL, ATTN_LOCAL, ModelConfig
from repro.obs import trace as obs_trace
from repro.kernels import ops
from repro.models import ImplConfig, build_model
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import transformer as T
from repro.serving.kv_cache import (PAGE_SIZE, PageGroups, Request,
                                    page_table)

KV_DTYPE = jnp.bfloat16


def kv_shape_key(cfg: ModelConfig, pool_pages: int, *,
                 use_rings: bool = True) -> Tuple:
    """KV shape signature deciding which paged tenants may alias one
    physical device array set: layer count, pool geometry, KV head
    layout, dtype, and (when rings are on) WHICH layers are rings --
    ring layers are indexed from the local id space, so a ring tenant
    and a no-ring tenant of the same config must not share arrays."""
    groups = PageGroups.from_config(cfg)
    rings = bool(use_rings) and groups.local_layers > 0
    return (cfg.num_blocks * len(cfg.pattern), int(pool_pages), PAGE_SIZE,
            cfg.num_kv_heads, cfg.head_dim, jnp.dtype(KV_DTYPE).name,
            tuple(k == ATTN_LOCAL for k in cfg.pattern) if rings else None)


class KVArrayStore:
    """One pod's physical KV page arrays for one KV shape: the aliasing
    unit of multi-tenant serving.

    Registered on the pod's :class:`~repro.serving.tenancy.SharedPagePool`
    keyed by :func:`kv_shape_key`; every same-shape paged tenant's
    :class:`PagedRunner` reads and writes THESE arrays (per-layer
    ``(pool_pages + 1, PAGE_SIZE, KV, hd)``, last slot = shared trash
    page), indexed by pod-unique physical page ids.  N same-model
    tenants therefore cost ONE pool of device HBM instead of N -- the
    pool's accounted footprint and the live footprint finally coincide.

    The arrays are engine-owned state, not any single runner's: jitted
    prefill/decode still donate them (in-place XLA updates), but each
    runner writes the donated result back here so co-tenants observe it.
    ``free_local`` is the shared physical id space for sliding-window
    ring pages (local-attention layers' arrays are shared too); it is
    None for shapes without rings.
    """

    def __init__(self, key: Tuple):
        (num_layers, pool_pages, page, kvh, hd, dtype, ring_pat) = key
        self.key = key
        self.num_layers = num_layers
        self.dtype = dtype
        self.page_shape = (pool_pages + 1, page, kvh, hd)
        self.k_pages: Optional[List[jax.Array]] = None
        self.v_pages: Optional[List[jax.Array]] = None
        self.free_local: Optional[List[int]] = (
            list(range(pool_pages)) if ring_pat and any(ring_pat) else None)
        self.users: set = set()     # app names aliasing this store
        self.ensure_arrays()

    def ensure_arrays(self) -> None:
        """(Re)materialize the device arrays -- parking the sole tenant
        drops them, and a later same-shape tenant (or unpark) needs them
        back."""
        if self.k_pages is None:
            self.k_pages = [jnp.zeros(self.page_shape, self.dtype)
                            for _ in range(self.num_layers)]
            self.v_pages = [jnp.zeros(self.page_shape, self.dtype)
                            for _ in range(self.num_layers)]

    def drop_arrays(self) -> None:
        self.k_pages = None
        self.v_pages = None

    def device_bytes(self) -> int:
        """Live device bytes of the page arrays (0 while parked-dropped)."""
        if self.k_pages is None:
            return 0
        return sum(int(a.nbytes) for a in self.k_pages) + \
            sum(int(a.nbytes) for a in self.v_pages)


def synth_prompt(req_id: str, prompt_len: int, vocab: int) -> jax.Array:
    """Deterministic synthetic prompt: stable across processes and runs."""
    seed = zlib.crc32(req_id.encode()) % 2**31
    return jax.random.randint(jax.random.PRNGKey(seed), (1, prompt_len),
                              0, vocab)


def prompt_for(req: Request, vocab: int) -> jax.Array:
    """(1, prompt_len) prompt tokens for a request.  An explicit
    ``req.prompt_tokens`` (benchmarks/tests controlling prompt overlap)
    wins; otherwise the usual deterministic synthesis.  BOTH backends go
    through here, so dense-vs-paged parity holds for either source."""
    if req.prompt_tokens is not None:
        assert len(req.prompt_tokens) == req.prompt_len
        return jnp.asarray(req.prompt_tokens, jnp.int32)[None, :]
    return synth_prompt(req.req_id, req.prompt_len, vocab)


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


class ModelRunner:
    """Backend interface the engine's step functions are bound to."""

    backend = "null"

    def __init__(self):
        self.engine = None
        self.generated: Dict[str, List[int]] = {}

    def bind(self, engine) -> None:
        self.engine = engine

    def prefill(self, req: Request) -> None:
        raise NotImplementedError

    def decode(self, running: List[Request]) -> None:
        raise NotImplementedError

    def finish(self, req: Request) -> None:
        """Completion hook (the engine calls this when a request is done):
        hand the tokens back to the request and evict every per-request
        runner entry -- a long-running engine must not accumulate state
        for requests that already left."""
        toks = self.generated.pop(req.req_id, None)
        if toks is not None:
            req.output_tokens = toks

    def close(self) -> None:
        """Drop this runner's device references; it is not used again.  A
        runner sits in reference cycles (with its engine, and with its
        jitted bound methods), so without this a retired replica keeps
        the weights alive until Python's cyclic collector runs."""
        self.params = None

    # -- idle parking (repro.autoscale.parking) ------------------------------
    @staticmethod
    def _tree_to_host(tree) -> Tuple[list, Any]:
        """Checkpointer array format (bf16 stored as uint16 + logical
        dtype) for a whole pytree; the device copies become collectable."""
        leaves, treedef = jax.tree.flatten(tree)
        return ([_to_savable(np.asarray(jax.device_get(x)))
                 for x in leaves], treedef)

    @staticmethod
    def _tree_from_host(saved: Tuple[list, Any]):
        leaves, treedef = saved
        return jax.tree.unflatten(
            treedef, [jnp.asarray(_from_saved(a, d)) for a, d in leaves])

    def park(self, drained: List[Tuple[Request, Tuple[List[int],
                                                      List[int]]]]) -> Dict:
        """Snapshot decode state AND params to host (checkpointer array
        format) and DROP the device copies, so a parked app's HBM is
        actually reclaimable -- the scheduler hands back 100% of the
        job's bytes, which must not leave weights silently resident.
        ``drained`` is the engine's ``drain()`` output: (request, (global
        page ids, local ring page ids) it held), with the page contents
        still intact on device."""
        state = {"generated": {k: list(v)
                               for k, v in self.generated.items()}}
        if getattr(self, "params", None) is not None:
            state["params"] = self._tree_to_host(self.params)
            self.params = None
        return state

    def unpark(self, state: Dict, restored: List[Request]) -> None:
        """Rebuild device state from a ``park`` snapshot.  ``restored``
        are the drained requests that re-acquired pages (their
        ``req.pages`` are fresh ids); requests that could not be
        re-granted are re-queued by the caller and re-prefill from
        scratch."""
        if "params" in state:
            self.params = self._tree_from_host(state["params"])
        self.generated = {k: list(v) for k, v in state["generated"].items()}

    # -- replica migration (serving.router.ReplicaSet) -----------------------
    #: replicas sharing one physical KV array set can hand running
    #: requests to each other without loss (paged); slot-indexed caches
    #: cannot (dense), so their requests take the requeue path instead
    can_migrate = False

    def migrate_out(self, drained: List[Tuple[Request, Tuple[List[int],
                                                             List[int]]]]
                    ) -> Dict:
        """Decode-state snapshot for replica-to-replica migration: park
        minus the params offload -- the surviving replicas keep serving,
        so weights stay on device and only the drained requests' state
        moves."""
        return {"generated": {req.req_id: self.generated.pop(req.req_id, [])
                              for req, _ in drained}}

    def migrate_in(self, state: Dict, restored: List[Request]) -> None:
        """Adopt migrated requests.  Unlike ``unpark`` (which REPLACES
        decode state wholesale), the target's own running requests keep
        theirs: only the restored requests' entries merge in."""
        for req in restored:
            self.generated[req.req_id] = list(
                state["generated"].get(req.req_id, []))


class DenseRunner(ModelRunner):
    """Slot-indexed dense KV cache; decode via ``model.decode_step``."""

    backend = "dense"

    def __init__(self, cfg: ModelConfig, *, seed: int = 0, max_batch: int = 4,
                 cache_len: int = 256, params=None):
        super().__init__()
        self.cfg = cfg
        self.max_batch = max_batch
        self.cache_len = cache_len
        self.model = build_model(cfg, ImplConfig(remat="none"))
        self.params = (params if params is not None
                       else self.model.init_params(jax.random.PRNGKey(seed)))

        # compile attribution: the tracer instants fire at XLA trace
        # time (Python, shapes are static ints), so each marks one
        # compile of this backend, not one call
        def _decode_body(p, toks, cache, pos):
            t = obs_trace.TRACER
            if t is not None:
                t.instant("compile", "decode_trace", None,
                          {"backend": "dense", "batch": toks.shape[0]})
            return self.model.decode_step(p, toks, cache, pos)

        def _prefill_body(p, b):
            t = obs_trace.TRACER
            if t is not None:
                t.instant("compile", "prefill_trace", None,
                          {"backend": "dense",
                           "tokens": b["tokens"].shape[1]})
            return self.model.prefill(p, b, cache_len)

        self._decode = jax.jit(_decode_body)
        self._prefill = jax.jit(_prefill_body)
        self.cache = self.model.init_cache(max_batch, cache_len)
        self.slots: Dict[str, Any] = {}

    def prefill(self, req: Request) -> None:
        toks = prompt_for(req, self.cfg.vocab_size)
        # zenlint: ignore[ZL003] -- dense prefill compiles per distinct
        # prompt length BY DESIGN: this backend also serves recurrent
        # families (SSM/RWKV) whose prefill state after padded tokens
        # cannot be masked back out, so length bucketing would change
        # outputs; the paged backend is the O(1)-compile serving path.
        logits, rc = self._prefill(self.params, {"tokens": toks})
        # evict slots of preempted requests (the engine re-queues them;
        # only completion frees a slot via finish) before picking one
        running_ids = {r.req_id for r in self.engine.running}
        for rid in list(self.slots):
            if rid not in running_ids:
                del self.slots[rid]
        if req.req_id in self.slots:      # re-admission after preemption
            slot = self.slots[req.req_id][0]
        else:
            slot = min(set(range(self.max_batch))
                       - {s for s, _ in self.slots.values()})
        self.slots[req.req_id] = (slot, req.prompt_len)
        self.cache = jax.tree.map(
            lambda full, one: jax.lax.dynamic_update_slice_in_dim(
                full, one.astype(full.dtype), slot, axis=1),
            self.cache, rc)
        # zenlint: ignore[ZL004] -- first-token extraction: prefill is
        # once per request (not per token) and the engine needs the
        # token id to seed decode; this is the designed sync point.
        self.generated[req.req_id] = [int(jnp.argmax(logits[0, -1]))]

    def decode(self, running: List[Request]) -> None:
        if not running:
            return
        s = zensan.SAN
        if s is not None:
            s.dense_state(self, running)
        toks = np.zeros((self.max_batch, 1), np.int32)
        pos = 0
        for req in running:
            slot, plen = self.slots[req.req_id]
            toks[slot, 0] = self.generated[req.req_id][-1]
            pos = max(pos, plen + req.generated)
        logits, self.cache = self._decode(
            self.params, jnp.asarray(toks), self.cache,
            jnp.asarray(pos, jnp.int32))
        # zenlint: ignore[ZL004] -- THE one batched device->host fetch
        # per decode step: every lane's next token in a single transfer.
        nxt = np.asarray(jnp.argmax(logits[:, -1], -1))
        for req in running:
            slot, _ = self.slots[req.req_id]
            self.generated[req.req_id].append(int(nxt[slot]))

    def finish(self, req: Request) -> None:
        super().finish(req)
        self.slots.pop(req.req_id, None)

    def close(self) -> None:
        super().close()
        self.cache = None

    def park(self, drained):
        """The dense cache is one contiguous tree: snapshot every leaf to
        host and drop the device copy."""
        state = super().park(drained)
        state["cache"] = self._tree_to_host(self.cache)
        state["slots"] = dict(self.slots)
        self.cache = None
        return state

    def unpark(self, state, restored):
        super().unpark(state, restored)
        self.cache = self._tree_from_host(state["cache"])
        self.slots = dict(state["slots"])


class PagedRunner(ModelRunner):
    """KV in pool pages; decode through the paged-attention kernel.

    Supports RoPE decoder-only stacks mixing ATTN_GLOBAL and ATTN_LOCAL
    blocks (llama- and gemma3-family patterns).  Global layers keep a
    page table that grows with sequence length; sliding-window layers
    keep a fixed per-request ring of ``PageGroups.ring_pages`` pages --
    decode writes token ``p`` at ring slot ``p % (ring_pages *
    PAGE_SIZE)`` and the kernel's ring masking recovers each slot's
    absolute position.  Other block kinds (SSM state, MoE, cross
    attention) keep the dense backend until they grow paged layouts.

    Device-memory note: the page arrays live in a :class:`KVArrayStore`
    -- pass ``kv_store=`` (the pod's registered store for this KV shape)
    and every same-shape tenant reads/writes ONE device allocation;
    without it the runner builds a private store (mismatched-shape and
    ``alias_kv=False`` tenants).  Requests carry view-local page ids; at
    kernel time the runner translates them through the engine pool's
    ``to_physical`` remap, so the kernel always indexes the arrays by
    pod-unique physical ids.  The last slot (index ``pool_pages``) is a
    write-only trash page for padded batch lanes.
    """

    backend = "paged"

    SUPPORTED_KINDS = (ATTN_GLOBAL, ATTN_LOCAL)

    def __init__(self, cfg: ModelConfig, *, seed: int = 0,
                 pool_pages: int = 128, max_batch: int = 4,
                 use_rings: bool = True,
                 kv_store: Optional[KVArrayStore] = None,
                 prefix_cache=None, chunk_pages: int = 4, params=None):
        super().__init__()
        if (any(k not in self.SUPPORTED_KINDS for k in cfg.pattern)
                or cfg.rope_theta <= 0 or cfg.is_encdec
                or cfg.family in ("vlm", "audio")):
            raise ValueError(
                f"backend='paged' supports RoPE global/sliding-window "
                f"attention stacks; {cfg.name} has pattern={cfg.pattern}")
        if ATTN_LOCAL in cfg.pattern and cfg.sliding_window <= 0:
            raise ValueError(f"{cfg.name}: ATTN_LOCAL needs sliding_window")
        self.cfg = cfg
        self.max_batch = max_batch
        self.groups = PageGroups.from_config(cfg)
        self.use_rings = use_rings and self.groups.local_layers > 0
        if prefix_cache is not None and self.groups.local_layers > 0:
            raise ValueError(
                f"prefix_cache=True needs a pure-global attention stack: "
                f"{cfg.name} has sliding-window layers whose ring pages "
                "cannot hold a position-stable shared prefix")
        self.prefix = prefix_cache
        self.chunk_pages = max(int(chunk_pages), 1)
        self.model = build_model(cfg, ImplConfig(remat="none"))
        self.params = (params if params is not None
                       else self.model.init_params(jax.random.PRNGKey(seed)))
        nb, pat = cfg.num_blocks, len(cfg.pattern)
        self.num_layers = nb * pat
        self.pool_pages = pool_pages
        self.trash_page = pool_pages            # padded lanes write here
        key = kv_shape_key(cfg, pool_pages, use_rings=self.use_rings)
        if kv_store is not None and kv_store.key != key:
            raise ValueError(
                f"kv_store shape mismatch for {cfg.name}: store key "
                f"{kv_store.key} != runner key {key} -- mismatched-shape "
                "tenants must fall back to private arrays")
        self.shared_kv = kv_store is not None
        self.store = kv_store if kv_store is not None else KVArrayStore(key)
        self.store.ensure_arrays()      # a parked-dropped store revives
        self.page_shape = self.store.page_shape
        # compile-count observability: incremented at TRACE time, so each
        # attribute counts XLA compiles, not calls (regression-tested)
        self.decode_traces = 0
        self.prefill_traces = 0
        # prefill work actually computed, in pages (the prefix cache's
        # savings metric: cached pages never reach this counter)
        self.prefill_pages_computed = 0
        self.reattach_unpins = 0
        # page arrays are donated: XLA updates them in place instead of
        # copying the whole pool per layer per token
        self._decode = jax.jit(self._decode_fn, donate_argnums=(9, 10))
        self._prefill = jax.jit(self._prefill_fn, donate_argnums=(6, 7))
        self._chunk = jax.jit(self._chunk_fn, donate_argnums=(8, 9))
        self._scatter = jax.jit(self._scatter_fn, donate_argnums=(0, 1))
        self._copy = jax.jit(self._copy_fn, donate_argnums=(0, 1))

    # the arrays live on the (possibly pod-shared) store; runner code and
    # tests read them through these aliases
    @property
    def k_pages(self) -> Optional[List[jax.Array]]:
        return self.store.k_pages

    @property
    def v_pages(self) -> Optional[List[jax.Array]]:
        return self.store.v_pages

    # -- view-local -> physical id translation -------------------------------
    def _phys(self, ids: List[int]) -> List[int]:
        """Physical ids of a request's global-table pages (identity for a
        private pool; the PoolView remap for pod-shared tenancy)."""
        pool = self.engine.pool if self.engine is not None else None
        return pool.to_physical(ids) if pool is not None else list(ids)

    def _phys_local(self, ids: List[int]) -> List[int]:
        pool = self.engine.pool if self.engine is not None else None
        return pool.to_physical_local(ids) if pool is not None else list(ids)

    def _layer_kind(self, layer: int) -> str:
        return self.cfg.pattern[layer % len(self.cfg.pattern)]

    def _layer_ring(self, layer: int) -> bool:
        """Whether this layer's table is a ring (vs a growing table)."""
        return self.use_rings and self._layer_kind(layer) == ATTN_LOCAL

    @staticmethod
    def _scatter_fn(kp, vp, pages, k, v):
        return (kp.at[pages].set(k.astype(KV_DTYPE)),
                vp.at[pages].set(v.astype(KV_DTYPE)))

    @staticmethod
    def _copy_fn(kp, vp, src, dst):
        """Copy-on-write page duplication (one layer's arrays, donated)."""
        return kp.at[dst].set(kp[src]), vp.at[dst].set(vp[src])

    def _cow_copy(self, src_phys: int, dst_phys: int) -> None:
        """Duplicate one physical page's KV across every layer (the
        insert-time self-COW: the donor keeps writing into the copy while
        the original becomes a read-only cached partial page)."""
        s = jnp.asarray(src_phys, jnp.int32)
        d = jnp.asarray(dst_phys, jnp.int32)
        for layer in range(self.num_layers):
            (self.store.k_pages[layer],
             self.store.v_pages[layer]) = self._copy(
                self.store.k_pages[layer], self.store.v_pages[layer], s, d)

    def _block_forward(self, bp, x, positions, mix):
        """One pattern block (the shared prefill/decode layer body).
        ``mix(q, k, v) -> (B, S, H, hd)`` carries the phase-specific
        part: writing KV into the page arrays and attending through the
        layer's table -- everything else must stay identical between the
        two phases or they diverge from dense in only one of them."""
        cfg = self.cfg
        h = T.apply_norm(cfg, bp["ln1"], x)
        q, k, v = attn.project_qkv(bp["attn"], h, cfg, positions)
        x = x + attn.attn_out(bp["attn"], mix(q, k, v))
        h = T.apply_norm(cfg, bp["ln2"], x)
        return x + L.gated_mlp(bp["mlp"], h)

    # -- prefill -------------------------------------------------------------
    def _prefill_fn(self, params, toks, last, g_ids, l_ids, l_src,
                    k_pages, v_pages):
        """Forward over the (page-padded) prompt, scattering each layer's
        KV page-by-page into the granted ids: no dense ``cache_len``
        cache, no per-grant-size recompile (the compile key is the padded
        prompt page count only).  ``last`` is the index of the final real
        prompt token; ``l_src`` names the prompt pages that survive in
        the ring (the last ``ring_pages`` of them)."""
        self.prefill_traces += 1
        t = obs_trace.TRACER
        if t is not None:
            t.instant("compile", "prefill_trace", None,
                      {"backend": "paged", "tokens": toks.shape[1]})
        cfg = self.cfg
        s = toks.shape[1]
        n_pg = s // PAGE_SIZE
        positions = jnp.arange(s)
        x = self.model._embed(params, toks)
        new_k, new_v = list(k_pages), list(v_pages)
        for layer in range(len(new_k)):
            j, i = divmod(layer, len(cfg.pattern))
            kind = cfg.pattern[i]
            bp = jax.tree.map(lambda a: a[j],
                              params["blocks"][f"p{i}_{kind}"])

            def mix(q, k, v, layer=layer, kind=kind):
                kpg = k[0].reshape(n_pg, PAGE_SIZE, cfg.num_kv_heads,
                                   cfg.head_dim).astype(KV_DTYPE)
                vpg = v[0].reshape(n_pg, PAGE_SIZE, cfg.num_kv_heads,
                                   cfg.head_dim).astype(KV_DTYPE)
                if self._layer_ring(layer):
                    new_k[layer] = new_k[layer].at[l_ids].set(kpg[l_src])
                    new_v[layer] = new_v[layer].at[l_ids].set(vpg[l_src])
                else:
                    new_k[layer] = new_k[layer].at[g_ids].set(kpg)
                    new_v[layer] = new_v[layer].at[g_ids].set(vpg)
                window = cfg.sliding_window if kind == ATTN_LOCAL else 0
                return attn.sdpa(q, k, v, causal=True, window=window,
                                 q_positions=positions,
                                 k_positions=positions)

            x = self._block_forward(bp, x, positions, mix)
        x = T.apply_norm(cfg, params["ln_f"], x)
        xl = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)
        logits = L.unembed(params["embed"], xl, cfg.logit_softcap)
        return jnp.argmax(logits[0, -1]), new_k, new_v

    def prefill(self, req: Request) -> None:
        """Forward over the prompt, scattering its KV page-by-page into
        the request's granted pages (global page p holds tokens
        [p*PAGE, (p+1)*PAGE); ring layers keep the last ``ring_pages``
        prompt pages at their ring slots).

        Pure-global stacks route through the CHUNKED path when a prefix
        cache is attached (suffix-only prefill + insert) or when the
        prompt exceeds one chunk (fixed-size chunks reuse O(chunk *
        log pool) compile buckets instead of one shape per prompt page
        count -- the PR 4 compile-key follow-up)."""
        assert req.pages or req.local_pages, \
            f"{req.req_id}: prefill before admission"
        cfg = self.cfg
        n_pg = -(-req.prompt_len // PAGE_SIZE)
        if (self.groups.local_layers == 0
                and (self.prefix is not None or n_pg > self.chunk_pages)):
            self._prefill_chunked(req)
            if self.prefix is not None:
                self._prefix_insert(req)
            return
        toks = prompt_for(req, cfg.vocab_size)
        pad = n_pg * PAGE_SIZE - req.prompt_len
        if pad:
            toks = jnp.pad(toks, ((0, 0), (0, pad)))
        if req.pages:
            g_ids = np.asarray(self._phys(req.pages[:n_pg]), np.int32)
        else:                               # pure-local stack: unused
            g_ids = np.full(n_pg, self.trash_page, np.int32)
        if self.use_rings:
            ring = self.groups.ring_pages
            # the last min(ring, n_pg) prompt pages survive, each at ring
            # slot (page % ring) -- consecutive pages hit distinct slots
            lp = self._phys_local(req.local_pages)
            l_src = np.arange(max(0, n_pg - ring), n_pg, dtype=np.int32)
            l_ids = np.asarray([lp[j % ring] for j in l_src], np.int32)
        else:
            l_src = np.zeros(0, np.int32)
            l_ids = np.zeros(0, np.int32)
        nxt, self.store.k_pages, self.store.v_pages = self._prefill(
            self.params, toks, jnp.asarray(req.prompt_len - 1, jnp.int32),
            jnp.asarray(g_ids), jnp.asarray(l_ids), jnp.asarray(l_src),
            self.store.k_pages, self.store.v_pages)
        self.prefill_pages_computed += n_pg
        # zenlint: ignore[ZL004] -- first-token extraction: once per
        # request at prefill, the designed sync point (see DenseRunner).
        self.generated[req.req_id] = [int(nxt)]

    # -- chunked / suffix-only prefill (pure-global stacks) ------------------
    def _chunk_fn(self, params, toks, lead, base, last, g_ids, cow_src,
                  ctx_table, k_pages, v_pages):
        """One prefill chunk: forward over ``toks`` (page-aligned chunk
        starting at absolute position ``base``), scatter its KV into the
        ``g_ids`` pages, and attend over (cached or earlier-chunk)
        context pages named by ``ctx_table`` (-1 padded, width bucketed)
        plus the chunk itself.

        Copy-on-write is FUSED: the first ``lead`` slots of chunk page 0
        are replaced with the cached partial page ``cow_src``'s content
        before scatter+attention, so one donated op yields a private page
        holding cached-lead + computed-suffix, and the attention keys for
        those positions are the true cached KV.  Cold path: lead=0,
        cow_src=trash, all-(-1) context.

        Compile key: (chunk page count, context-table bucket) only --
        lead/base/last/cow_src are traced scalars, so warm and cold
        prefills of any offset share compiles."""
        self.prefill_traces += 1
        t = obs_trace.TRACER
        if t is not None:
            t.instant("compile", "chunk_trace", None,
                      {"backend": "paged", "tokens": toks.shape[1],
                       "ctx_w": ctx_table.shape[0]})
        cfg = self.cfg
        s = toks.shape[1]
        n_pg = s // PAGE_SIZE
        w = ctx_table.shape[0]
        positions = base + jnp.arange(s)
        k_pos = jnp.concatenate([jnp.arange(w * PAGE_SIZE), positions])
        k_valid = jnp.concatenate(
            [jnp.repeat(ctx_table >= 0, PAGE_SIZE),
             jnp.ones(s, bool)])
        lead_mask = (jnp.arange(PAGE_SIZE) < lead)[:, None, None]
        x = self.model._embed(params, toks)
        new_k, new_v = list(k_pages), list(v_pages)
        for layer in range(len(new_k)):
            j, i = divmod(layer, len(cfg.pattern))
            kind = cfg.pattern[i]
            bp = jax.tree.map(lambda a: a[j],
                              params["blocks"][f"p{i}_{kind}"])

            def mix(q, k, v, layer=layer):
                kpg = k[0].reshape(n_pg, PAGE_SIZE, cfg.num_kv_heads,
                                   cfg.head_dim)
                vpg = v[0].reshape(n_pg, PAGE_SIZE, cfg.num_kv_heads,
                                   cfg.head_dim)
                kpg = kpg.at[0].set(jnp.where(
                    lead_mask, new_k[layer][cow_src].astype(k.dtype),
                    kpg[0]))
                vpg = vpg.at[0].set(jnp.where(
                    lead_mask, new_v[layer][cow_src].astype(v.dtype),
                    vpg[0]))
                new_k[layer] = new_k[layer].at[g_ids].set(
                    kpg.astype(KV_DTYPE))
                new_v[layer] = new_v[layer].at[g_ids].set(
                    vpg.astype(KV_DTYPE))
                # context pages are read back AFTER the scatter: they are
                # disjoint from g_ids (strictly earlier absolute pages),
                # so the gather sees cached/earlier-chunk KV only
                ctx_k = new_k[layer][jnp.maximum(ctx_table, 0)].reshape(
                    1, w * PAGE_SIZE, cfg.num_kv_heads,
                    cfg.head_dim).astype(k.dtype)
                ctx_v = new_v[layer][jnp.maximum(ctx_table, 0)].reshape(
                    1, w * PAGE_SIZE, cfg.num_kv_heads,
                    cfg.head_dim).astype(v.dtype)
                k_cat = jnp.concatenate(
                    [ctx_k, kpg.reshape(1, s, cfg.num_kv_heads,
                                        cfg.head_dim)], axis=1)
                v_cat = jnp.concatenate(
                    [ctx_v, vpg.reshape(1, s, cfg.num_kv_heads,
                                        cfg.head_dim)], axis=1)
                return attn.sdpa(q, k_cat, v_cat, causal=True,
                                 q_positions=positions, k_positions=k_pos,
                                 k_valid=k_valid)

            x = self._block_forward(bp, x, positions, mix)
        x = T.apply_norm(cfg, params["ln_f"], x)
        xl = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)
        logits = L.unembed(params["embed"], xl, cfg.logit_softcap)
        return jnp.argmax(logits[0, -1]), new_k, new_v

    def _prefill_chunked(self, req: Request) -> None:
        """Suffix-only prefill in absolute-grid chunks.  The first
        ``req.cached_len`` prompt tokens are already in cache pages
        (``req.shared_pages`` + a COW lead); computation starts at the
        cached page boundary and each chunk ends on a multiple of
        ``chunk_pages`` -- warm and cold runs of the same prompt see
        IDENTICAL chunk boundaries past the cached region, so their
        attention math (and tokens) agree exactly."""
        cfg = self.cfg
        toks = prompt_for(req, cfg.vocab_size)
        total_pg = -(-req.prompt_len // PAGE_SIZE)
        pad = total_pg * PAGE_SIZE - req.prompt_len
        if pad:
            toks = jnp.pad(toks, ((0, 0), (0, pad)))
        cached = req.cached_len
        pages_all = list(req.shared_pages) + self._phys(req.pages)
        assert len(pages_all) >= total_pg, \
            f"{req.req_id}: {len(pages_all)} pages < prompt {total_pg}"
        p = cached // PAGE_SIZE        # == len(req.shared_pages)
        nxt = None
        tr = obs_trace.TRACER
        while p < total_pg:
            n_pg = min(self.chunk_pages - p % self.chunk_pages,
                       total_pg - p)
            s0 = p * PAGE_SIZE
            lead = cached - s0 if s0 < cached else 0
            ctx_w = _next_pow2(max(p, 1))
            ctx = np.full(ctx_w, -1, np.int32)
            ctx[:p] = pages_all[:p]
            g_ids = np.asarray(pages_all[p:p + n_pg], np.int32)
            last = min(req.prompt_len - 1 - s0, n_pg * PAGE_SIZE - 1)
            cow_id = (req.cow_src_page
                      if lead and req.cow_src_page is not None
                      else self.trash_page)
            nxt, self.store.k_pages, self.store.v_pages = self._chunk(
                self.params, toks[:, s0:s0 + n_pg * PAGE_SIZE],
                jnp.asarray(lead, jnp.int32), jnp.asarray(s0, jnp.int32),
                jnp.asarray(last, jnp.int32), jnp.asarray(g_ids),
                jnp.asarray(cow_id, jnp.int32), jnp.asarray(ctx),
                self.store.k_pages, self.store.v_pages)
            self.prefill_pages_computed += n_pg
            if tr is not None:
                tr.instant("request", "prefill_chunk", req.req_id,
                           {"start_page": p, "pages": n_pg, "lead": lead})
            p += n_pg
        if self.prefix is not None and cached % PAGE_SIZE:
            # partial-page hit: the fused lead copy above IS the COW
            self.prefix.stats["cow_copies"] += 1
        self.generated[req.req_id] = [int(nxt)]

    # -- prefix-cache lifecycle ----------------------------------------------
    def _host_prompt(self, req: Request) -> Tuple[int, ...]:
        """The request's prompt token ids as a host tuple (the trie key).
        Synthesized prompts are fetched from device ONCE per request and
        memoized on ``req.prompt_tokens``, which also pins the prompt for
        parking's re-attach lookup."""
        if req.prompt_tokens is None:
            toks = synth_prompt(req.req_id, req.prompt_len,
                                self.cfg.vocab_size)
            req.prompt_tokens = tuple(
                int(t) for t in np.asarray(toks[0]))
        return req.prompt_tokens

    def prefix_attach(self, req: Request) -> None:
        """Pre-admission lookup+pin: match the prompt against the trie,
        pin the chain, and record the shared-page layout on the request
        so the pool charges only the private suffix.  The engine calls
        this right before ``try_admit`` and detaches (pool-side) if
        admission fails."""
        if self.prefix is None or req.prefix_nodes is not None:
            return
        m = self.prefix.pin(self._host_prompt(req),
                            max_len=req.prompt_len - 1)
        req.prefix_nodes = m.nodes
        req.shared_pages = list(m.phys_pages)
        req.cached_len = m.cached_len
        req.cow_src_page = m.cow_src
        t = obs_trace.TRACER
        if t is not None:
            t.instant("request", "prefix_pin", req.req_id,
                      {"cached_len": m.cached_len,
                       "shared_pages": len(m.phys_pages),
                       "cow": m.cow_src is not None})

    def _prefix_insert(self, req: Request) -> None:
        """Post-prefill donation: move the prompt's freshly computed full
        pages out of the view's accounting into the cache (the request
        keeps referencing them, now as pinned shared pages), and donate
        the partial tail page after a self-COW (grant a replacement page,
        copy the tail into it, hand the original to the cache).  A race
        -- another request inserted the same prefix this tick -- adopts
        nothing: probe_new sizes the donation at 0 and this request just
        keeps its private copies."""
        cache = self.prefix
        toks = self._host_prompt(req)
        n_full = req.prompt_len // PAGE_SIZE
        rem = req.prompt_len % PAGE_SIZE
        n_att = len(req.shared_pages)
        pool = self.engine.pool if self.engine is not None else None
        if pool is None or n_att > n_full:
            return
        n_new, partial_new = cache.probe_new(toks, n_att)
        phys: List[int] = []
        if n_new:
            phys = pool.cache_donate(req.pages[:n_new])
            del req.pages[:n_new]
            req.shared_pages.extend(phys)
        partial_phys = None
        if partial_new and rem and n_att + n_new == n_full:
            got = pool.cow_grant()
            if got is not None:
                # after the slice above, the partial tail page is the
                # request's first remaining private page
                src = self._phys(req.pages[:1])[0]
                dst = self._phys(got)[0]
                self._cow_copy(src, dst)
                partial_phys = pool.cache_donate(req.pages[:1])[0]
                req.pages[0] = got[0]
                cache.stats["cow_copies"] += 1
        if phys or partial_phys is not None:
            created = cache.insert(toks, n_att, phys,
                                   partial_page=partial_phys)
            req.prefix_nodes = (req.prefix_nodes or []) + created
            t = obs_trace.TRACER
            if t is not None:
                t.instant("request", "prefix_insert", req.req_id,
                          {"donated": len(phys),
                           "partial": partial_phys is not None})

    def prefix_reattach(self, req: Request) -> bool:
        """Unpark: re-pin the shared prefix chain a parked request was
        decoding through.  The pages may have moved (evicted and
        re-inserted by another tenant) but the token chain is the key,
        so any surviving chain of ``parked_shared`` full nodes is
        content-identical.  False = some node was evicted while parked:
        the caller must requeue the request for a from-scratch recompute."""
        if req.parked_shared == 0:
            return True
        if self.prefix is None:
            return False
        m = self.prefix.pin(self._host_prompt(req),
                            max_full=req.parked_shared)
        if len(m.phys_pages) < req.parked_shared:
            self.reattach_unpins += self.prefix.unpin(m.nodes)
            return False
        req.prefix_nodes = m.nodes
        req.shared_pages = list(m.phys_pages)
        return True

    # -- decode --------------------------------------------------------------
    def _decode_fn(self, params, toks, positions, phys_g, phys_l, off,
                   table_g, table_l, vlen, k_pages, v_pages):
        """One batched decode step over the whole stack (jitted; the page
        arrays are donated so per-layer writes happen in place).  Each
        layer writes at its group's physical page (growing table vs ring)
        and attends through its group's page table."""
        self.decode_traces += 1
        t = obs_trace.TRACER
        if t is not None:
            t.instant("compile", "decode_trace", None,
                      {"backend": "paged", "batch": toks.shape[0],
                       "table_w": table_g.shape[1]})
        cfg = self.cfg
        w = cfg.sliding_window
        new_k, new_v = list(k_pages), list(v_pages)
        x = self.model._embed(params, toks)
        for layer in range(len(new_k)):
            j, i = divmod(layer, len(cfg.pattern))
            kind = cfg.pattern[i]
            bp = jax.tree.map(lambda a: a[j],
                              params["blocks"][f"p{i}_{kind}"])

            def mix(q, k, v, layer=layer, kind=kind):
                ring = self._layer_ring(layer)
                phys = phys_l if ring else phys_g
                kp = new_k[layer].at[phys, off].set(
                    k[:, 0].astype(KV_DTYPE))
                vp = new_v[layer].at[phys, off].set(
                    v[:, 0].astype(KV_DTYPE))
                new_k[layer], new_v[layer] = kp, vp
                o = ops.paged_attention(
                    q[:, 0], kp, vp, table_l if ring else table_g, vlen,
                    window=w if kind == ATTN_LOCAL else 0, ring=ring)
                return o[:, None]

            x = self._block_forward(bp, x, positions, mix)
        x = T.apply_norm(cfg, params["ln_f"], x)
        logits = L.unembed(params["embed"], x, cfg.logit_softcap)
        return jnp.argmax(logits[:, -1], -1), new_k, new_v

    def decode_inputs(self, running: List[Request]) -> Tuple[jax.Array, ...]:
        """The per-step arguments of ``_decode_fn`` for ``running``, after
        ``params`` and before the page arrays: tokens, positions, write
        pages (global, ring), write offsets, page tables (global, ring)
        and valid lengths, padded to ``max_batch`` lanes."""
        b = self.max_batch
        assert len(running) <= b, f"{len(running)} running > max_batch {b}"
        ring = self.groups.ring_pages if self.use_rings else 1
        pos = np.asarray([r.length for r in running])     # write positions
        for r, p in zip(running, pos):
            if ((r.pages or r.shared_pages)
                    and p // PAGE_SIZE >= len(r.shared_pages) + len(r.pages)):
                raise RuntimeError(
                    f"{r.req_id}: token {p} beyond granted pages "
                    f"({len(r.shared_pages)} shared + {len(r.pages)}) -- "
                    "engine must grow with horizon=1")
            if (self.use_rings
                    and (p // PAGE_SIZE) % ring >= len(r.local_pages)):
                raise RuntimeError(
                    f"{r.req_id}: token {p} beyond granted ring pages "
                    f"({len(r.local_pages)}/{ring})")
        # batch is padded to max_batch: idle lanes write into the trash
        # page with an all-masked table, so the compile key is constant
        # in batch size; the table width is bucketed to the next power of
        # two so a growing widest-grant re-buckets O(log pool) times.
        # Tables and write slots carry PHYSICAL ids (requests hold
        # view-local ones): the kernel indexes the possibly pod-shared
        # device arrays, where only physical ids are unique.  A request
        # with a cached prefix mixes BOTH id classes in one table: its
        # read-only shared pages (already physical, cache-owned) lead,
        # its view-translated private pages follow; decode always writes
        # past the prefix, so only private pages are ever written.
        g_phys = [list(r.shared_pages) + self._phys(r.pages)
                  for r in running]
        l_phys = ([self._phys_local(r.local_pages) for r in running]
                  if self.use_rings else [[] for _ in running])
        s = zensan.SAN
        if s is not None:
            # runtime twin of zenlint ZL001: every id entering the
            # table must be this view's grant or a cache page
            s.table(self.engine.pool if self.engine is not None else None,
                    g_phys, l_phys)
        maxp_b = _next_pow2(max(max(len(p) for p in g_phys), 1))
        toks = np.zeros((b, 1), np.int32)
        positions = np.zeros((b, 1), np.int32)
        offs = np.zeros(b, np.int32)
        vlen = np.ones(b, np.int32)
        phys_g = np.full(b, self.trash_page, np.int32)
        phys_l = np.full(b, self.trash_page, np.int32)
        table_g = np.full((b, maxp_b), -1, np.int32)
        table_g[:len(running)] = page_table(running, maxp_b, pages=g_phys)
        table_l = np.full((b, ring), -1, np.int32)
        for i, (r, p) in enumerate(zip(running, pos)):
            toks[i, 0] = self.generated[r.req_id][-1]
            positions[i, 0] = p
            offs[i] = p % PAGE_SIZE
            vlen[i] = p + 1
            if g_phys[i]:
                phys_g[i] = g_phys[i][p // PAGE_SIZE]
            if self.use_rings:
                phys_l[i] = l_phys[i][(p // PAGE_SIZE) % ring]
                table_l[i, :len(l_phys[i])] = l_phys[i]
        return tuple(jnp.asarray(a) for a in (toks, positions, phys_g, phys_l,
                                              offs, table_g, table_l, vlen))

    def decode(self, running: List[Request]) -> None:
        if not running:
            return
        nxt, self.store.k_pages, self.store.v_pages = self._decode(
            self.params, *self.decode_inputs(running),
            self.store.k_pages, self.store.v_pages)
        # zenlint: ignore[ZL004] -- THE one batched device->host fetch
        # per decode step (all lanes' tokens in one transfer); every
        # other read below indexes this host copy.
        nxt = np.asarray(nxt)
        for i, req in enumerate(running):
            self.generated[req.req_id].append(int(nxt[i]))

    # -- parking -------------------------------------------------------------
    def park(self, drained):
        """Snapshot ONLY the view's pages: gather each drained request's
        KV to host (per layer group: one (layers, n_pages, PAGE, KV, hd)
        array for the growing tables and one for the rings -- ``drained``
        carries the *physical* ids ``reclaim`` translated before
        freeing).  The pool-sized device arrays are dropped only when no
        co-tenant still decodes through the shared store: an aliased
        tenant's real reclamation is its pages returning to the shared
        free list, where the co-tenants immediately reuse them."""
        state = super().park(drained)
        state["kv"] = self._gather_drained(drained)
        # drop the device arrays unless a co-tenant still decodes through
        # them: a PARKED co-tenant doesn't count (its KV is already
        # snapshotted to host, and unpark revives the arrays), so the
        # last active tenant to park takes the pool's HBM with it
        pool = self.engine.pool if self.engine is not None else None
        own = getattr(pool, "app", None)
        views = getattr(getattr(pool, "shared", None), "views", {})
        sole = all(getattr(views.get(u), "parked", False)
                   for u in self.store.users if u != own)
        if sole:
            # cached prefix pages live inside these arrays: flush them
            # (every pin was dropped when the tenants' requests were
            # reclaimed) so the index doesn't outlive the content
            shared = getattr(pool, "shared", None)
            if shared is not None:
                shared.flush_prefix_caches(self.store.key)
            elif self.prefix is not None:
                self.prefix.flush()
            self.store.drop_arrays()
        state["arrays_dropped"] = sole
        return state

    def unpark(self, state, restored):
        super().unpark(state, restored)
        self.store.ensure_arrays()      # no-op when co-tenants kept them
        self._scatter_restored(state["kv"], restored)

    def _layer_split(self):
        table_layers = [l for l in range(self.num_layers)
                        if not self._layer_ring(l)]
        ring_layers = [l for l in range(self.num_layers)
                       if self._layer_ring(l)]
        return table_layers, ring_layers

    def _gather_drained(self, drained):
        """Host snapshot of each drained request's KV, keyed by request:
        per layer group one (layers, n_pages, PAGE, KV, hd) array for the
        growing tables and one for the rings.  ``drained`` carries the
        *physical* ids ``reclaim`` translated before freeing."""
        table_layers, ring_layers = self._layer_split()

        def gather(layers, ids):
            if not layers or not ids:
                return None
            idx = jnp.asarray(ids, jnp.int32)
            k = np.stack([np.asarray(self.k_pages[l][idx]) for l in layers])
            v = np.stack([np.asarray(self.v_pages[l][idx]) for l in layers])
            return (_to_savable(k), _to_savable(v))

        kv = {}
        for req, (g_ids, l_ids) in drained:
            kv[req.req_id] = {"g": gather(table_layers, g_ids),
                              "l": gather(ring_layers, l_ids)}
        return kv

    def _scatter_restored(self, kv, restored):
        """Write gathered KV back at each restored request's CURRENT
        grants -- ``self._phys`` maps through this runner's own view, so
        the same helper serves unpark (same view, fresh ids) and replica
        migration (target view, same physical arrays)."""
        table_layers, ring_layers = self._layer_split()
        for req in restored:
            saved = kv[req.req_id]
            for layers, ids, packed in ((table_layers, self._phys(req.pages),
                                         saved["g"]),
                                        (ring_layers,
                                         self._phys_local(req.local_pages),
                                         saved["l"])):
                if packed is None:
                    continue
                (ka, kd), (va, vd) = packed
                k = jnp.asarray(_from_saved(ka, kd))   # (L, n, PAGE, KV, hd)
                v = jnp.asarray(_from_saved(va, vd))
                pages = jnp.asarray(ids, jnp.int32)
                for li, layer in enumerate(layers):
                    (self.store.k_pages[layer],
                     self.store.v_pages[layer]) = self._scatter(
                        self.store.k_pages[layer],
                        self.store.v_pages[layer], pages, k[li], v[li])

    can_migrate = True

    def migrate_out(self, drained):
        state = super().migrate_out(drained)
        state["kv"] = self._gather_drained(drained)
        return state

    def migrate_in(self, state, restored):
        super().migrate_in(state, restored)
        self._scatter_restored(state["kv"], restored)


def build_runner(backend: str, cfg: ModelConfig, *, seed: int = 0,
                 max_batch: int = 4, cache_len: int = 256,
                 pool_pages: int = 128, use_rings: bool = True,
                 kv_store: Optional[KVArrayStore] = None,
                 prefix_cache=None, chunk_pages: int = 4,
                 params=None) -> ModelRunner:
    """Factory keyed by ``Application.options['backend']``.  ``kv_store``
    aliases the paged backend onto the pod's shared device arrays;
    ``prefix_cache`` attaches the pod's global prefix cache (paged only:
    the dense backend has no page identity to share, so asking for a
    cache there is REJECTED rather than silently dropped -- a benchmark
    must never compare a cached arm against one that quietly never
    cached).  ``params`` reuses an existing weight tree (a replica of the
    same model) instead of initializing one."""
    if backend == "dense":
        if prefix_cache is not None:
            raise ValueError(
                "backend='dense' cannot serve prefix_cache=True: the "
                "dense KV cache has no shareable page identity; use "
                "backend='paged' or drop the option")
        return DenseRunner(cfg, seed=seed, max_batch=max_batch,
                           cache_len=cache_len, params=params)
    if backend == "paged":
        return PagedRunner(cfg, seed=seed, pool_pages=pool_pages,
                           max_batch=max_batch, use_rings=use_rings,
                           kv_store=kv_store, prefix_cache=prefix_cache,
                           chunk_pages=chunk_pages, params=params)
    raise ValueError(f"unknown serving backend {backend!r} "
                     "(expected 'dense' or 'paged')")
