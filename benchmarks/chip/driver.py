"""Drive the system under test through the path its users take.

``Cluster`` -> ``Application.serve(..., ServeOptions(backend="paged"))``
-> ``AppHandle.submit_request`` / ``AppHandle.step`` -> ``RequestRouter``
-> ``ReplicaSet`` -> ``ServingEngine`` -> ``PagedRunner`` -> the compiled
Pallas kernel ``ops.paged_attention``.  One process, no children.

The harness keeps its own record of the run on the host clock: when each
request was due and submitted, when each of its tokens reached the host
(stamped after every ``AppHandle.step``), every step's span, and the
runner's ``prefill``/``decode`` calls, which it wraps at set-up in
``jax.profiler.TraceAnnotation`` spans so that a device trace can be
read against them.
"""

from __future__ import annotations

import heapq
import math
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.runtime import Application, Cluster, JaxExecutor
from repro.runtime.options import ServeOptions
from repro.serving.kv_cache import PAGE_SIZE, Request

clock = time.perf_counter


@dataclass
class Tracked:
    """The harness's view of one request."""

    spec: Any
    due: float
    req: Request
    submitted: float = 0.0
    stamps: List[float] = field(default_factory=list)   # one per token
    done: bool = False
    admitted_cached: Optional[int] = None

    @property
    def prompt_len(self) -> int:
        return len(self.spec.prompt)


def next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


#: the throwaway prompts of the warm-up are drawn from this fixed stream
WARM_STREAM = 7


def prefill_keys(total: int, start: int, chunk_pages: int,
                 cached: bool) -> Set[Tuple]:
    """What prefilling a prompt of ``total`` pages from cached page
    ``start`` compiles, as the runner prefills it: whole-prompt prefill
    of ``total`` pages (no prefix cache, one chunk or less), or chunks
    on the absolute grid of ``chunk_pages``, each a program keyed by
    (pages, context-width bucket) and a slice of the padded prompt keyed
    by (total, pages)."""
    if not cached and total <= chunk_pages:
        return {("native", total)}
    keys: Set[Tuple] = set()
    p = start
    while p < total:
        n = min(chunk_pages - p % chunk_pages, total - p)
        keys.add(("chunk", n, next_pow2(max(p, 1))))
        keys.add(("slice", total, n))
        p += n
    return keys


def warm_plan(lengths: Sequence[int], max_tokens: int, chunk_pages: int,
              cached: bool) -> Tuple[List[Tuple[int, int]], List[int]]:
    """The warm-up's requests: (prompt length, cached start page) pairs
    that between them take every prefill path a prompt no longer than
    the longest of ``lengths`` can take (from every cached start, with
    the prefix cache; from page 0 without), each of ``lengths`` among
    them, the cheapest first; and the prompt lengths whose one decode
    lands in each table-width bucket of sequences up to ``max_tokens``."""
    max_pages = max((-(-n // PAGE_SIZE) for n in lengths), default=0)
    by_pages: Dict[int, int] = {}
    for n in sorted(lengths):
        by_pages[-(-n // PAGE_SIZE)] = n

    def starts(total: int) -> range:
        return range(total) if cached else range(1)

    need: Set[Tuple] = set()
    for total in range(1, max_pages + 1):
        for s in starts(total):
            need |= {k for k in prefill_keys(total, s, chunk_pages, cached)
                     if k[0] != "slice" or total in by_pages}
    plan: List[Tuple[int, int]] = []
    covered: Set[Tuple] = set()
    for n in sorted(set(lengths)):
        total = -(-n // PAGE_SIZE)
        s = max(starts(total), key=lambda s: (
            len(prefill_keys(total, s, chunk_pages, cached) - covered)
            - (total - s), s))
        plan.append((n, s))
        covered |= prefill_keys(total, s, chunk_pages, cached)
    cands = [(total, s, prefill_keys(total, s, chunk_pages, cached))
             for total in range(1, max_pages + 1) for s in starts(total)]
    while need - covered:
        total, s, keys = max(cands, key=lambda c: (
            len((c[2] & need) - covered) / (c[0] - c[1]), c[1]))
        plan.append((by_pages.get(total, total * PAGE_SIZE), s))
        covered |= keys
    widths = sorted({next_pow2(p)
                     for p in range(1, -(-max_tokens // PAGE_SIZE) + 1)})
    return plan, [w * PAGE_SIZE - PAGE_SIZE // 2 for w in widths]


def serve(cfg: ModelConfig, serve_opts: Dict, *, seed: int, mesh,
          name: str):
    """Submit one paged serve application on a fresh one-pod cluster; no
    history, so nothing outside the checkout feeds its sizing.  The
    invocation class asks for the pool the configuration states."""
    pool_pages = int(serve_opts["pool_pages"])
    max_batch = int(serve_opts["max_batch"])
    seq = -(-pool_pages * PAGE_SIZE // max_batch)
    cluster = Cluster(pods=1, mesh=mesh, history=None,
                      executor=JaxExecutor(seed=seed))
    app = Application.serve(
        cfg, shape=ShapeConfig(f"{name}_decode", "decode", seq, max_batch),
        name=name,
        serve=ServeOptions(backend="paged", max_batch=max_batch,
                           pool_pages=pool_pages,
                           prefix_cache=bool(serve_opts["prefix_cache"]),
                           chunk_pages=serve_opts.get("chunk_pages")))
    handle = cluster.submit(app)
    if handle.state != "running":
        raise RuntimeError(f"{name}: not admitted (state={handle.state}, "
                           f"demand={handle.job.demand_bytes} bytes, "
                           f"HBM/device={mesh.hbm_per_device})")
    return cluster, handle


class Run:
    """One run of one cell: set-up, the window, and the record."""

    def __init__(self, handle, source, *, trace_dir: Optional[str] = None):
        self.handle = handle
        self.source = source
        self.runner = handle.runner
        self.engine = handle.engine
        self.trace_dir = trace_dir
        self.tracked: Dict[str, Tracked] = {}
        self.steps: List[Tuple[float, float, int, int]] = []
        self.decodes: List[Tuple[float, float, List[int]]] = []
        self.prefills: List[Tuple[float, float, str, int, int]] = []
        self.lateness: List[float] = []
        self.window: Tuple[float, float] = (0.0, 0.0)
        self.trace_window: Optional[Tuple[float, float]] = None
        self._seq = 0
        self._wrap_runner()

    # -- instrumentation -----------------------------------------------------
    def _wrap_runner(self) -> None:
        """Wrap the runner's two entry points: a TraceAnnotation span
        each (so the device trace can be read against them) and the
        harness's own record of what each call computed."""
        runner, rec_d, rec_p = self.runner, self.decodes, self.prefills
        prefill, decode = runner.prefill, runner.decode

        def traced_prefill(req):
            t0 = clock()
            with jax.profiler.TraceAnnotation("chipbench.prefill"):
                prefill(req)
            rec_p.append((t0, clock(), req.req_id, req.prompt_len,
                          req.cached_len))

        def traced_decode(running):
            lens = [r.length + 1 for r in running]
            t0 = clock()
            with jax.profiler.TraceAnnotation("chipbench.decode"):
                decode(running)
            rec_d.append((t0, clock(), lens))

        runner.prefill, runner.decode = traced_prefill, traced_decode
        for rep in self.handle.replica_set.replicas:
            rep.engine.step_fns = (traced_prefill, traced_decode)

    def pool_pages_used(self) -> Tuple[int, int]:
        shared = self.engine.pool.shared
        return shared.used_pages, shared.num_pages

    # -- set-up --------------------------------------------------------------
    def warm(self, lengths: Sequence[int], max_tokens: int,
             vocab: int) -> int:
        """Serve, before the window, throwaway requests through
        ``AppHandle.submit_request`` / ``AppHandle.step`` that take every
        path the traffic can take, so that nothing compiles inside the
        window: a prompt of each length the traffic can send (the
        runner's per-length prompt operations), prompts that start
        prefill from every cached page the prefix cache can hand them
        (every chunk and context-width shape), and a decode at every
        table-width bucket up to ``max_tokens``.  Cached starts come from
        a long prompt served before each batch of requests.  The prefix
        cache is emptied of the throwaway prompts after.  Returns the
        number of requests."""
        r = self.runner
        cached = r.prefix is not None
        plan, decodes = warm_plan(lengths, max_tokens, r.chunk_pages, cached)
        g = np.random.default_rng(WARM_STREAM)
        base: Tuple[int, ...] = ()
        if cached:
            base = tuple(int(t) for t in g.integers(
                0, vocab, -(-max_tokens // PAGE_SIZE) * PAGE_SIZE - 1))
        served = 0

        def make(n: int, start: int) -> Request:
            nonlocal served
            served += 1
            fresh = g.integers(0, vocab, n - start * PAGE_SIZE)
            toks = base[:start * PAGE_SIZE] + tuple(int(t) for t in fresh)
            return Request(f"warm{served}", n, 1, prompt_tokens=toks)

        def serve(reqs: List[Request]) -> None:
            for q in reqs:
                self.handle.submit_request(q)
            while self.handle.step()["alive"]:
                pass

        def serve_base() -> None:
            nonlocal served
            served += 1
            serve([Request(f"warm{served}", len(base), 1,
                           prompt_tokens=base)])

        for i in range(0, len(plan), r.max_batch):
            if cached:
                # refreshed before each batch, so that the cache evicts
                # older throwaway prompts before any page of it
                serve_base()
            serve([make(n, s) for n, s in plan[i:i + r.max_batch]])
        for n in decodes:
            serve([make(n, max(-(-n // PAGE_SIZE) - 1, 0) if cached else 0)])
        if cached:
            r.prefix.flush()
        jax.block_until_ready(r.store.k_pages)
        # the record holds the traffic's own calls only
        self.decodes.clear()
        self.prefills.clear()
        return served

    def fill_prefixes(self, prompts) -> None:
        """Serve each prompt (one token after the first) to completion
        before the window, so the prefix cache holds it."""
        for i, p in enumerate(prompts):
            self.handle.submit_request(Request(f"prefix{i}", len(p), 1,
                                               prompt_tokens=tuple(p)))
        while self.handle.step()["alive"]:
            pass

    def start_setup_requests(self, specs) -> None:
        """Submit requests that are already under way when the window
        opens (a closed loop's staggered clients) and take one step, so
        the window starts with them admitted."""
        now = clock()
        for s in specs:
            self._submit(s, now)
        self._step()
        self._stamp(clock())

    # -- the window ----------------------------------------------------------
    def _submit(self, spec, due: float) -> None:
        req = Request(spec.id, len(spec.prompt), spec.max_new,
                      prompt_tokens=tuple(spec.prompt))
        tr = Tracked(spec, due, req)
        self.tracked[spec.id] = tr
        tr.submitted = clock()
        self.handle.submit_request(req)

    def _step(self) -> bool:
        t0 = clock()
        with jax.profiler.TraceAnnotation("chipbench.step"):
            alive = self.handle.step()["alive"]
        t1 = clock()
        used, total = self.pool_pages_used()
        self.steps.append((t0, t1, used, total))
        return alive

    def _stamp(self, now: float) -> List[Tracked]:
        """Stamp every token that reached the host in the last step;
        return the requests that finished in it."""
        finished = []
        for tr in self.tracked.values():
            if tr.done:
                continue
            req = tr.req
            if req.output_tokens is not None:
                n = len(req.output_tokens)
                tr.done = True
                finished.append(tr)
            elif req.first_token_at is not None:
                n = 1 + req.generated
            else:
                n = 0
            if n > len(tr.stamps):
                tr.stamps.extend([now] * (n - len(tr.stamps)))
            if tr.admitted_cached is None and req.first_token_at is not None:
                tr.admitted_cached = req.cached_len
        return finished

    def window_loop(self, seconds: float, trace_seconds: float = 0.0
                    ) -> None:
        """Offer the traffic for ``seconds`` from now: submit each request
        when it is due, step while there is work, sleep otherwise.  With
        ``trace_seconds`` the profiler traces the window's last stretch,
        started and stopped between steps."""
        t0 = clock()
        t_end = t0 + seconds
        pending: List = []
        for off, spec in self.source.arrivals():
            if off < seconds:
                heapq.heappush(pending, (t0 + off, self._push_seq(), spec))
        trace_at = t_end - trace_seconds if trace_seconds > 0 else math.inf
        tracing = False
        alive = True
        while True:
            now = clock()
            if now >= t_end:
                break
            if not tracing and now >= trace_at and self.trace_dir:
                jax.profiler.start_trace(self.trace_dir)
                tracing = True
                self.trace_window = (clock(), 0.0)
            while pending and pending[0][0] <= now:
                due, _, spec = heapq.heappop(pending)
                self.lateness.append(now - due)
                self._submit(spec, due)
                alive = True
            if not alive:
                nxt = min(pending[0][0] if pending else t_end, t_end,
                          trace_at if not tracing else t_end)
                time.sleep(max(nxt - clock(), 0.0))
                continue
            alive = self._step()
            after = clock()
            for tr in self._stamp(after):
                for off, spec in self.source.on_finish(
                        tr.spec, tr.req.output_tokens, after - t0):
                    if t0 + off < t_end:
                        heapq.heappush(pending, (t0 + off, self._push_seq(),
                                                 spec))
        t_close = clock()
        if tracing:
            self.trace_window = (self.trace_window[0], t_close)
            jax.profiler.stop_trace()
        self.window = (t0, t_close)
        self.nominal_end = t_end

    def _push_seq(self) -> int:
        self._seq += 1
        return self._seq

    def outputs(self) -> Dict[str, Tuple[Tuple[int, ...], List[int], bool]]:
        """Prompt and served tokens of every request, finished or not."""
        out = {}
        gen = self.runner.generated
        for rid, tr in self.tracked.items():
            toks = tr.req.output_tokens
            if toks is None:
                toks = list(gen.get(rid, []))
            out[rid] = (tuple(tr.spec.prompt), list(toks), tr.done)
        return out
