"""Put the program's own spans on the device trace's clock, and split the
device's idle time by the span that held it.

The request tracer (``repro.obs.trace``) stamps its spans with
``time.perf_counter``; the profiler stamps its planes with a clock of
its own.  ``Tracer.anchor()``, called right after the profiler starts
and right before it stops, leaves one ``obs``/``clock`` instant in the
ring (``t_in`` and ``t_out``: ``perf_counter`` just before and just
after) and one ``repro.obs.clock`` annotation on the trace's host plane.
The anchors, paired in order, give a linear map from ``perf_counter``
seconds to trace nanoseconds, fitted on their midpoints, so drift
between the two clocks is fitted and not ignored.  Each idle stretch of
the device between the anchors is then put down to the innermost
program span covering it (spans nest by time on the one host thread),
and what no span covers to ``outside program spans``.

    python3 benchmarks/chip/attribution.py --workload <cell> --seed <n> \\
        --seconds <s>

makes one traced run of a cell (``run.py --trace 1``, on the chip) with
the two anchors placed, prints ``run.py``'s result line, the attribution
table on standard error, and last one JSON line: the fit's residual and
drift, the attributed idle beside the run's ``device_idle_share``,
seconds of idle per span name, ``step_host_ms``, ``prefill_idle_ms``,
``kv_used_share`` read from the ``engine``/``step`` spans beside the
harness's own reading, and the traced run's end-to-end numbers (to
compare with an untraced run of the same seed).  ``run.py`` and
``driver.py`` are used as they are: the anchors and the ring are
reached from outside them.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (HERE, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import tails  # noqa: E402
import trace_reduce  # noqa: E402
from repro.obs.trace import CLOCK_ANNOTATION  # noqa: E402

#: the label of idle time that no program span covers
OUTSIDE = "outside program spans"
#: the spans whose time ``step_host_ms`` takes out of a step: the host
#: waiting on the device for tokens
FETCHES = ("runner/token_fetch", "runner/first_token_fetch")

Interval = Tuple[float, float]
Span = Tuple[float, float, str]


@dataclass
class ClockFit:
    """``trace_ns = offset_ns + rate * perf_counter_s``.  ``residual_ns``
    is the largest distance, over the anchors, between an edge of the
    anchor's annotation and the mapped ``perf_counter`` reading beside
    it: how far a mapped span edge may lie from where the profiler
    would have put it."""

    offset_ns: float
    rate: float
    residual_ns: float

    def __call__(self, t: float) -> float:
        return self.offset_ns + self.rate * t

    @property
    def drift_ppm(self) -> float:
        return (self.rate / 1e9 - 1.0) * 1e6


def fit_clock(ring: Sequence[Interval], trace: Sequence[Interval]
              ) -> Optional[ClockFit]:
    """The map from the anchors: ``ring`` holds each anchor's
    (``t_in``, ``t_out``) in seconds, ``trace`` its annotation's (start,
    end) in nanoseconds, in the same order.  A least-squares line through
    the midpoints; one anchor gives the offset alone (rate 1e9).  None
    without anchors or when the two lists disagree in length."""
    if not ring or len(ring) != len(trace):
        return None
    xs = [(a + b) / 2 for a, b in ring]
    ys = [(a + b) / 2 for a, b in trace]
    n = len(xs)
    mx, my = sum(xs) / n, sum(ys) / n
    sxx = sum((x - mx) ** 2 for x in xs)
    rate = (sum((x - mx) * (y - my) for x, y in zip(xs, ys)) / sxx
            if n > 1 and sxx > 0 else 1e9)
    fit = ClockFit(my - rate * mx, rate, 0.0)
    fit.residual_ns = max(max(abs(fit(ti) - s), abs(fit(to) - e))
                          for (ti, to), (s, e) in zip(ring, trace))
    return fit


def ring_anchors(events) -> List[Interval]:
    """(``t_in``, ``t_out``) of each ``obs``/``clock`` instant, oldest
    first."""
    return sorted((e[6]["t_in"], e[6]["t_out"]) for e in events
                  if e[3] == "obs" and e[4] == "clock")


def program_spans(events, fit: Optional[ClockFit] = None) -> List[Span]:
    """The ring's complete spans as (start, end, ``cat/name``), on the
    trace's clock in nanoseconds when ``fit`` is given, else in
    ``perf_counter`` seconds."""
    f = fit or (lambda t: t)
    return sorted((f(ts), f(ts + dur), f"{cat}/{name}")
                  for ts, dur, ph, cat, name, _, _ in events if ph == "X")


def idle_intervals(busy: Sequence[Interval], lo: float, hi: float
                   ) -> List[Interval]:
    """[lo, hi] less the union of ``busy``."""
    out, at = [], lo
    for a, b in trace_reduce.union(busy):
        if b <= at:
            continue
        if a >= hi:
            break
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if at < hi:
        out.append((at, hi))
    return out


def attribute_idle(busy: Sequence[Interval], spans: Sequence[Span],
                   lo: float, hi: float) -> Dict[str, float]:
    """Idle time of [lo, hi] (outside every ``busy`` interval) by the
    innermost span covering each part of it -- of the spans open there,
    the one that started last (the shorter on a tie) -- and
    :data:`OUTSIDE` for parts no span covers.  The parts sum to the
    idle time."""
    live = [i for i, (a, b, _) in enumerate(spans) if b > a]
    marks = sorted([(spans[i][0], 1, i) for i in live]
                   + [(spans[i][1], 0, i) for i in live])
    out: Dict[str, float] = {}
    open_: Dict[int, Span] = {}
    j = 0
    for ga, gb in idle_intervals(busy, lo, hi):
        at = ga
        while at < gb:
            while j < len(marks) and marks[j][0] <= at:
                _, starts, i = marks[j]
                if starts:
                    open_[i] = spans[i]
                else:
                    open_.pop(i, None)
                j += 1
            end = min(gb, marks[j][0]) if j < len(marks) else gb
            label = (max(open_.values(), key=lambda s: (s[0], -s[1]))[2]
                     if open_ else OUTSIDE)
            out[label] = out.get(label, 0.0) + end - at
            at = end
    return out


def step_host_ms(spans: Sequence[Span], lo: float, hi: float
                 ) -> Optional[float]:
    """Median, over the ``engine/step`` spans that start in [lo, hi]
    (``perf_counter`` seconds), of each step's duration less the time of
    the token fetches inside it (:data:`FETCHES`): the host's own time
    in a step, in milliseconds."""
    steps = [s for s in spans if s[2] == "engine/step" and lo <= s[0] <= hi]
    fetches = [s for s in spans if s[2] in FETCHES]
    own = []
    for a, b, _ in steps:
        waited = sum(fb - fa for fa, fb, _ in fetches if a <= fa and fb <= b)
        own.append(b - a - waited)
    v = tails.median(own)
    return None if v is None else 1e3 * v


def step_kv_used_share(events, lo: float, hi: float) -> Optional[float]:
    """Mean share, in percent, of the pool's pages in use at the end of
    each ``engine``/``step`` span that starts in [lo, hi]
    (``perf_counter`` seconds), from the span's ``pages_used`` and
    ``pool_pages``: the harness's ``kv_used_share``, read at the
    program's step boundary instead of from outside."""
    xs = [e[6]["pages_used"] / e[6]["pool_pages"] for e in events
          if e[3] == "engine" and e[4] == "step" and lo <= e[0] <= hi
          and e[6]["pool_pages"]]
    return 100.0 * sum(xs) / len(xs) if xs else None


def prefill_idle_ms(busy: Sequence[Interval], spans: Sequence[Span],
                    lo: float, hi: float) -> Optional[float]:
    """Device idle time inside the ``request/prefill`` spans that lie
    wholly in [lo, hi] (trace nanoseconds), per such span, in
    milliseconds."""
    pre = [(a, b) for a, b, name in spans
           if name == "request/prefill" and lo <= a and b <= hi]
    if not pre:
        return None
    idle = sum(sum(y - x for x, y in idle_intervals(busy, a, b))
               for a, b in pre)
    return idle / len(pre) / 1e6


def read_trace(path: str) -> Tuple[List[List[Interval]], List[Interval]]:
    """From a profile: each device's operation intervals (one list per
    ``/device:TPU:<n>`` plane with an ``XLA Ops`` line) and the
    (start, end) of each :data:`CLOCK_ANNOTATION` on the host plane,
    in nanoseconds."""
    from jax.profiler import ProfileData
    devices: List[List[Interval]] = []
    anchors: List[Interval] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            for ln in plane.lines:
                if ln.name == "XLA Ops":
                    devices.append([(e.start_ns, e.start_ns + e.duration_ns)
                                    for e in ln.events])
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                anchors += [(e.start_ns, e.start_ns + e.duration_ns)
                            for e in ln.events
                            if e.name == CLOCK_ANNOTATION]
    return devices, sorted(anchors)


def attribute(events, devices: Sequence[Sequence[Interval]],
              trace_anchors: Sequence[Interval]) -> Optional[Dict]:
    """Everything the table needs from the ring's ``events`` and a
    profile read by :func:`read_trace`: the clock fit, the stretch
    between the first anchor's end and the last one's start, the idle
    seconds by span (averaged over the devices), their share of the
    stretch, and ``prefill_idle_ms``.  None without two anchors on both
    sides."""
    ring = ring_anchors(events)
    fit = fit_clock(ring, trace_anchors)
    if fit is None or len(ring) < 2:
        return None
    lo, hi = trace_anchors[0][1], trace_anchors[-1][0]
    spans = program_spans(events, fit)
    n = max(len(devices), 1)
    idle: Dict[str, float] = {}
    for ops in devices or [[]]:
        for k, v in attribute_idle(ops, spans, lo, hi).items():
            idle[k] = idle.get(k, 0.0) + v / n / 1e9
    pre = [prefill_idle_ms(ops, spans, lo, hi) for ops in devices or [[]]]
    pre = [v for v in pre if v is not None]
    return {"residual_ms": fit.residual_ns / 1e6,
            "drift_ppm": fit.drift_ppm,
            "stretch_s": (hi - lo) / 1e9,
            "idle_s": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
            "idle_share": 100.0 * sum(idle.values()) / ((hi - lo) / 1e9),
            "prefill_idle_ms": sum(pre) / len(pre) if pre else None}


@contextlib.contextmanager
def _anchored(captured: Dict):
    """While the run lasts: anchor the tracer right after the profiler
    starts and right before it stops, and keep the tracer ``run.py``
    enables and the record its metric readers are handed."""
    import jax
    import registry
    from repro.obs import trace as obs_trace
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace
    enable, reader = obs_trace.enable, registry.metric_reader

    def start_trace(*a, **k):
        start(*a, **k)
        obs_trace.TRACER.anchor()

    def stop_trace():
        obs_trace.TRACER.anchor()
        stop()

    def enable_kept(*a, **k):
        captured["tracer"] = enable(*a, **k)
        return captured["tracer"]

    def reader_kept(name, **k):
        read = reader(name, **k)

        def kept(record):
            captured["record"] = record
            return read(record)
        return kept

    jax.profiler.start_trace, jax.profiler.stop_trace = start_trace, \
        stop_trace
    obs_trace.enable, registry.metric_reader = enable_kept, reader_kept
    try:
        yield
    finally:
        jax.profiler.start_trace, jax.profiler.stop_trace = start, stop
        obs_trace.enable, registry.metric_reader = enable, reader


def main(argv: Optional[List[str]] = None, **run_kwargs) -> int:
    """One traced run and its attribution; ``run_kwargs`` go to
    ``run.main`` (the benchmark's own tests pass the CPU settings)."""
    import registry
    import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(sys.argv[1:] if argv is None else argv)
    captured: Dict = {}
    out = io.StringIO()
    with _anchored(captured), contextlib.redirect_stdout(out):
        rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                       "--seconds", str(args.seconds), "--trace", "1"],
                      **run_kwargs)
    lines = out.getvalue().strip().splitlines()
    for ln in lines:
        print(ln, flush=True)
    if rc != 0 or not lines or "tracer" not in captured:
        return rc or 1
    result = json.loads(lines[-1])
    record, events = captured["record"], captured["tracer"].snapshot()
    path = trace_reduce.find_trace(run.TRACE_DIR)
    devices, anchors = read_trace(path) if path else ([], [])
    table = attribute(events, devices, anchors) or {}
    w0, w1 = record["window"]
    table["step_host_ms"] = step_host_ms(program_spans(events), w0, w1)
    bench_dir = run_kwargs.get("bench_dir", HERE)
    table["kv_used_share"] = {
        "program": step_kv_used_share(events, w0, w1),
        "harness": registry.metric_reader("kv_used_share",
                                          bench_dir=bench_dir)(record)}
    tr = record.get("trace") or {}
    if tr.get("window_s"):
        table["device_idle_share"] = 100.0 * (1 - tr["busy_s"]
                                              / tr["window_s"])
    root = run_kwargs.get("root", ROOT)
    cell = registry.load_cell(args.workload, root=root, bench_dir=bench_dir)
    table["end_to_end"] = {
        m["name"]: registry.metric_reader(m["name"], bench_dir=bench_dir)(
            record) for m in cell.end_to_end}
    table["correct"] = result["correct"]
    for name, secs in (table.get("idle_s") or {}).items():
        run.say(f"idle {secs * 1e3:12.3f} ms  {name}")
    run.say(f"clock fit residual {table.get('residual_ms')} ms, drift "
            f"{table.get('drift_ppm')} ppm; attributed idle "
            f"{table.get('idle_share')}% of the anchored stretch, "
            f"device_idle_share {table.get('device_idle_share')}%")
    print(json.dumps(table), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
