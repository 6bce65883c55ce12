"""Proactive compilation cache: the paper's pre-warm / pre-launch analog.

Paper §5.2.1 pre-launches the next component's environment while the current
one runs and caches runtime compilations per component layout (§4.2: "once
the runtime compiles a version for one invocation, it is cached and reused
for future invocations with the same component layouts").

TPU adaptation: the expensive environment setup is XLA compilation.  The
cache keys on (arch, shape, mesh, plan-layout) -- the "component layout" --
and stores compiled executables in-process; JAX's persistent compilation
cache (placed by ``configure_persistent_cache``) gives cross-process reuse.
``prewarm`` compiles the *next* expected invocation class on a background
thread while the current one executes (hiding setup behind the critical
path, Fig. 7/23)."""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Any, Callable, Dict

from repro.core.materializer import Plan

#: the persistent cache's home when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: a fixed, git-ignored directory of the checkout (the path is part of
#: the cache key, so it must not move between runs)
REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def configure_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache lives in ``REPO_CACHE_DIR``.
    Entry points call this before their first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR


def plan_layout_key(arch: str, shape: str, mesh: str, plan: Plan) -> str:
    """The paper's 'component layout' identity."""
    d = plan.describe()
    d.pop("notes", None)
    d.pop("est_bytes_per_device", None)
    blob = json.dumps({"arch": arch, "shape": shape, "mesh": mesh, **d},
                      sort_keys=True, default=str)
    return hashlib.sha1(blob.encode()).hexdigest()[:16]


@dataclass
class CacheEntry:
    key: str
    compiled: Any
    compile_time_s: float
    hits: int = 0
    created: float = field(default_factory=time.time)


class CompileCache:
    def __init__(self):
        self._entries: Dict[str, CacheEntry] = {}
        self._lock = threading.Lock()
        self._inflight: Dict[str, threading.Event] = {}
        self.stats = {"hits": 0, "misses": 0, "prewarmed": 0,
                      "prewarm_hits": 0}

    def get_or_compile(self, key: str, build: Callable[[], Any]) -> Any:
        """Blocking fetch; compiles on miss (single-flight per key)."""
        with self._lock:
            ent = self._entries.get(key)
            if ent is not None:
                ent.hits += 1
                self.stats["hits"] += 1
                return ent.compiled
            ev = self._inflight.get(key)
            if ev is None:
                ev = threading.Event()
                self._inflight[key] = ev
                owner = True
            else:
                owner = False
        if not owner:
            ev.wait()
            with self._lock:
                ent = self._entries.get(key)
                if ent is not None:
                    self.stats["hits"] += 1
                    return ent.compiled
            # the owner failed: retry as the new owner
            return self.get_or_compile(key, build)
        t0 = time.time()
        try:
            compiled = build()
        except BaseException:
            # release waiters (they retry) and pass the error on
            with self._lock:
                self._inflight.pop(key, None)
            ev.set()
            raise
        with self._lock:
            self.stats["misses"] += 1
            self._entries[key] = CacheEntry(key, compiled, time.time() - t0)
            self._inflight.pop(key, None)
        ev.set()
        return compiled

    def prewarm(self, key: str, build: Callable[[], Any]) -> Future:
        """Compile ahead of time on a background thread (pre-launch).  The
        returned future carries the compile's result or its exception."""
        fut: Future = Future()

        def work():
            try:
                compiled = self.get_or_compile(key, build)
            except Exception as e:
                fut.set_exception(e)
                return
            with self._lock:
                self.stats["prewarmed"] += 1
            fut.set_result(compiled)

        threading.Thread(target=work, daemon=True).start()
        return fut

    def contains(self, key: str) -> bool:
        with self._lock:
            return key in self._entries
