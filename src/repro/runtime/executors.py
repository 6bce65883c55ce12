"""Execution backends for the runtime: the simulator and real jax share
ONE submission path and differ only in the executor bound at submit time.

* :class:`NullExecutor` -- no jax, no device state.  Training steps are
  no-ops and serving engines run without step functions: exactly what the
  scheduler-scalability and placement benchmarks need (pure decision
  throughput, like the paper's §6.2 measurement).
* :class:`JaxExecutor` -- builds the model, compiles the step through the
  CompileCache, feeds synthetic data, writes async checkpoints, and runs
  real prefill/decode through the ServingEngine.

Executors keep all per-application state on ``handle.exec_state`` so one
executor instance can drive many applications on one cluster.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, TYPE_CHECKING

from repro.core.compile_cache import CompileCache, plan_layout_key
from repro.runtime.options import ServeOptions
from repro.serving.engine import ServingEngine
from repro.serving.kv_cache import PagePool

if TYPE_CHECKING:  # pragma: no cover - typing only
    from repro.runtime.cluster import AppHandle
    from repro.serving.router import Replica

DEFAULT_POOL_PAGES = 256


class Executor:
    """Interface the AppHandle lifecycle drives."""

    name = "null"
    default_pool_pages = DEFAULT_POOL_PAGES
    default_max_batch = 8

    def bind(self, handle: "AppHandle") -> None:
        """Materialize executable state for a placed application."""
        if handle.app.kind == "serve":
            self._bind_serve(handle)

    @staticmethod
    def serve_opts(handle: "AppHandle") -> ServeOptions:
        """The app's typed serve surface (directly-constructed
        Applications may still carry a legacy options dict)."""
        so = getattr(handle.app, "serve_options", None)
        if so is not None:
            return so
        return ServeOptions.from_kwargs(handle.app.options or {})

    def _bind_serve(self, handle: "AppHandle") -> None:
        """Serve data plane: a ReplicaSet of engines registered with the
        pod's RequestRouter.  ``exec_state['engine']`` stays the primary
        replica's engine (the stable single-engine surface tests and
        tools already consume)."""
        from repro.serving.router import ReplicaSet
        opts = self.serve_opts(handle)
        rset = ReplicaSet(handle.app.name,
                          lambda idx: self.build_replica(handle, idx),
                          initial=opts.replicas, app_weight=opts.weight,
                          quota_pages=opts.quota_pages
                          if isinstance(opts.quota_pages, int) else None)
        try:
            handle.cluster.router(handle.pod).register(handle.app.name, rset)
        except Exception:
            rset.shutdown()
            raise
        handle.exec_state["replicas"] = rset
        handle.exec_state["engine"] = rset.primary.engine

    def train_step(self, handle: "AppHandle") -> Dict[str, float]:
        return {"loss": 0.0}

    def build_pool(self, handle: "AppHandle",
                   view_name: Optional[str] = None) -> PagePool:
        """The application's KV page pool.

        Default: a quota/weight-scoped *view* onto the pod's single
        :class:`~repro.serving.tenancy.SharedPagePool`, so every serve app
        placed on one pod draws from one physical pool (the paper's
        resource sharing).  ``ServeOptions.private_pool`` opts out into
        the old one-pool-per-app peak provisioning (the benchmark's
        baseline arm).

        Replica views carry suffixed names (``view_name``) but one
        per-app ``history_key``, so N replicas feed one sizing-history
        series instead of fragmenting it.

        When the app serves through the paged backend on a mixed
        global/sliding-window stack, the pool carries the model's
        :class:`~repro.serving.kv_cache.PageGroups` so local-attention
        layers are charged a bounded ring instead of the growing table
        (``swa_rings=False`` opts out, the benchmark's no-ring arm)."""
        opts = self.serve_opts(handle)
        pages = int(opts.pool_pages or self.default_pool_pages)
        groups = None
        if (opts.backend == "paged" and handle.app.config is not None
                and opts.swa_rings):
            from repro.serving.kv_cache import PageGroups
            g = PageGroups.from_config(handle.app.config)
            groups = g if g.local_layers else None
        if opts.private_pool:
            return PagePool(pages, history=handle.cluster.history,
                            app=handle.app.name, policy=opts.policy,
                            groups=groups)
        shared = handle.cluster.pod_pool(handle.pod, default_pages=pages)
        return shared.view(view_name or handle.app.name,
                           quota=opts.quota_pages, weight=opts.weight,
                           policy=opts.policy, groups=groups,
                           history_key=handle.app.name)

    def build_replica(self, handle: "AppHandle", idx: int) -> "Replica":
        from repro.serving.router import Replica, replica_view_name
        opts = self.serve_opts(handle)
        pool = self.build_pool(
            handle, view_name=replica_view_name(handle.app.name, idx))
        eng = ServingEngine(pool,
                            max_batch=opts.max_batch or self.default_max_batch,
                            history=handle.cluster.history)
        return Replica(idx, eng)

    def maybe_checkpoint(self, handle: "AppHandle") -> None:
        pass

    def checkpoint(self, handle: "AppHandle", block: bool = True) -> None:
        pass

    def restore(self, handle: "AppHandle") -> int:
        """Restore the latest persisted cut; returns the restart cursor."""
        return 0

    def release(self, handle: "AppHandle") -> None:
        rset = handle.exec_state.get("replicas")
        if rset is not None:
            handle.cluster.router(handle.pod).unregister(handle.app.name)
            rset.shutdown()    # return pages to the pod's shared pool
        else:
            engine = handle.exec_state.get("engine")
            if engine is not None:
                engine.shutdown()
        handle.exec_state.clear()


class NullExecutor(Executor):
    """Placement/accounting only -- drives the event-driven simulator."""


class JaxExecutor(Executor):
    """Real execution: jit-compiled training steps / model-backed serving."""

    name = "jax"

    def __init__(self, *, ckpt_dir: Optional[str] = None, ckpt_every: int = 0,
                 resume: bool = False, seed: int = 0,
                 opt_cfg: Optional[Any] = None,
                 compile_cache: Optional[CompileCache] = None):
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self.resume = resume
        self.seed = seed
        self.opt_cfg = opt_cfg
        self.cache = compile_cache or CompileCache()

    def _ckpt_dir(self, handle: "AppHandle") -> Optional[str]:
        """Per-application checkpoint namespace: one executor drives many
        applications, which must not overwrite each other's cuts."""
        if not self.ckpt_dir:
            return None
        import os
        return os.path.join(self.ckpt_dir, handle.app.name.replace("/", "_"))

    # -- binding ------------------------------------------------------------
    def bind(self, handle: "AppHandle") -> None:
        if handle.app.kind == "train":
            self._bind_train(handle)
        else:
            self._bind_serve(handle)

    def _bind_train(self, handle: "AppHandle") -> None:
        import jax

        from repro.checkpoint.checkpointer import AsyncCheckpointer
        from repro.data.pipeline import DataConfig, SyntheticLM
        from repro.models import ImplConfig, build_model
        from repro.training import optimizer as opt
        from repro.training.train_step import make_train_step

        app, plan = handle.app, handle.plan
        cfg, shape = app.config, app.shape
        # reduced CPU runs keep remat off: the ladder's remat choice targets
        # pod HBM budgets, not the smoke-scale footprint
        impl = ImplConfig(remat="none" if app.reduced else plan.remat)
        model = build_model(cfg, impl)
        rng = jax.random.PRNGKey(self.seed)
        params = model.init_params(rng)
        opt_state = opt.init_opt_state(params)
        key = plan_layout_key(cfg.name, shape.name, plan.mesh.name, plan)
        step = self.cache.get_or_compile(
            key, lambda: jax.jit(make_train_step(model, plan, self.opt_cfg)))
        data = SyntheticLM(DataConfig(cfg.vocab_size, shape.seq_len,
                                      shape.global_batch))
        ckpt_dir = self._ckpt_dir(handle)
        ck = AsyncCheckpointer(ckpt_dir, keep=3) if ckpt_dir else None
        handle.exec_state.update(model=model, params=params,
                                 opt_state=opt_state, step=step, data=data,
                                 checkpointer=ck)
        if self.resume:
            handle.cursor = max(handle.cursor, self.restore(handle))

    # -- training -----------------------------------------------------------
    def train_step(self, handle: "AppHandle") -> Dict[str, float]:
        import jax.numpy as jnp

        st = handle.exec_state
        batch = {k: jnp.asarray(v)
                 for k, v in st["data"].batch_at(handle.cursor).items()}
        st["params"], st["opt_state"], m = st["step"](
            st["params"], st["opt_state"], batch)
        return {"loss": float(m["loss"])}

    def maybe_checkpoint(self, handle: "AppHandle") -> None:
        if (self.ckpt_every and handle.exec_state.get("checkpointer")
                and handle.cursor % self.ckpt_every == 0):
            self.checkpoint(handle, block=False)

    def checkpoint(self, handle: "AppHandle", block: bool = True) -> None:
        ck = handle.exec_state.get("checkpointer")
        if ck is None:
            return
        st = handle.exec_state
        ck.save(handle.cursor, {"params": st["params"], "opt": st["opt_state"]},
                extra={"cursor": handle.cursor}, block=block)

    def restore(self, handle: "AppHandle") -> int:
        from repro.checkpoint.checkpointer import (latest_step,
                                                   restore_checkpoint)
        ckpt_dir = self._ckpt_dir(handle)
        if not ckpt_dir or latest_step(ckpt_dir) is None:
            return 0
        st = handle.exec_state
        tree = {"params": st["params"], "opt": st["opt_state"]}
        restored, extra, _ = restore_checkpoint(ckpt_dir, None, tree)
        st["params"], st["opt_state"] = restored["params"], restored["opt"]
        return int(extra.get("cursor", 0))

    # -- serving ------------------------------------------------------------
    default_pool_pages = 128
    default_max_batch = 4

    def build_replica(self, handle: "AppHandle", idx: int) -> "Replica":
        from repro.serving.model_runner import (KVArrayStore, PagedRunner,
                                                build_runner, kv_shape_key)
        from repro.serving.prefix_cache import PrefixCache
        from repro.serving.router import Replica, replica_view_name

        app = handle.app
        opts = self.serve_opts(handle)
        max_batch = opts.max_batch or self.default_max_batch
        # both backends pad decode to the runner's build-time batch, so a
        # batch-scaling policy gets its headroom baked into the compile
        # shape up front: the engine's admission width then moves within
        # it with zero retraces
        runner_batch = max_batch
        if opts.scale is not None and opts.scale.batch_max is not None:
            runner_batch = max(runner_batch, opts.scale.batch_max)
        backend = opts.backend
        use_rings = opts.swa_rings
        pool = self.build_pool(
            handle, view_name=replica_view_name(app.name, idx))
        prim = handle.exec_state.get("runner")
        # replicas serve one model: alias the primary's weights so a
        # replica costs compute slots, not a second params copy (nor the
        # transient one initializing it would take)
        params = (prim.params if idx > 0 and prim is not None
                  and prim.backend == backend else None)
        try:
            kv_store = None
            if (backend == "paged"
                    and getattr(pool, "shared", None) is not None
                    and opts.alias_kv
                    and all(k in PagedRunner.SUPPORTED_KINDS
                            for k in app.config.pattern)):
                # physical aliasing: every same-KV-shape paged tenant on
                # this pod -- and every replica of one app -- reads/writes
                # ONE device page-array set, keyed by shape (mismatched
                # shapes get their own store, i.e. fall back to private
                # arrays; alias_kv=False opts out explicitly)
                key = kv_shape_key(app.config, pool.physical_pages,
                                   use_rings=use_rings)
                kv_store = pool.shared.kv_store(
                    key, lambda: KVArrayStore(key))
                pool.bind_kv_store(kv_store)
            prefix_cache = None
            if opts.prefix_cache:
                if kv_store is not None:
                    # pod-global cache: keyed by (kv shape, model, seed)
                    # -- same-weights tenants share cached prefixes, and
                    # the cache's pages return to the POD free list
                    ck = (kv_store.key, app.config.name, self.seed)
                    shared = pool.shared
                    prefix_cache = shared.prefix_cache(
                        ck, lambda: PrefixCache(ck, shared._give))
                    prefix_cache.users.add(app.name)
                else:
                    # private pool (or un-aliased tenant): a private cache
                    # still dedups this app's own prompt overlap.  Evicted
                    # pages must return to whatever free list GRANTED
                    # them: the pod's for a shared-pool view (its own
                    # `free` list is a dead stub -- extending it would
                    # leak the pages from the pod forever), the pool's
                    # own otherwise
                    shared = getattr(pool, "shared", None)
                    free_fn = (shared._give if shared is not None
                               else pool._give)
                    prefix_cache = PrefixCache(
                        (None, app.config.name, self.seed), free_fn)
                pool.prefix_cache = prefix_cache
            runner = build_runner(backend, app.config,
                                  seed=self.seed, max_batch=runner_batch,
                                  cache_len=opts.cache_len,
                                  pool_pages=pool.physical_pages,
                                  use_rings=use_rings, kv_store=kv_store,
                                  prefix_cache=prefix_cache,
                                  chunk_pages=opts.chunk_pages or 4,
                                  params=params)
        except Exception:
            # the pool view is already registered on the pod: an orphan
            # would dilute every tenant's fair share forever (close also
            # unbinds the kv store, dropping it with its last user)
            close = getattr(pool, "close", None)
            if close is not None:
                close()
            raise
        eng = ServingEngine(pool, max_batch=max_batch, runner=runner,
                            history=handle.cluster.history)
        if idx == 0:
            handle.exec_state.update(model=runner.model,
                                     params=runner.params, runner=runner)
        return Replica(idx, eng, runner=runner)

    def release(self, handle: "AppHandle") -> None:
        ck = handle.exec_state.get("checkpointer")
        if ck is not None:
            ck.wait()
        super().release(handle)
