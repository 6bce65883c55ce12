"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time, time per device operation, the paged-attention
kernel's events, the host spans the benchmark placed, and the longest
idle gaps labelled by what the host was doing in them.

Only JAX's own reader (``jax.profiler.ProfileData``) is used.  A device
plane is one named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one
event per operation, named by the operation's HLO text, and its ``XLA
Modules`` line one event per program run.  The host plane ``/host:CPU``
holds the ``jax.profiler.TraceAnnotation`` spans (``chipbench.*``).
Times in the trace are nanoseconds.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Sequence, Tuple

#: host spans the harness places, innermost first (idle-gap labels)
HOST_SPANS = ("chipbench.prefill", "chipbench.decode", "chipbench.step")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")


def operands(hlo: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """(dtype, dims) of each operand of a custom call's HLO text."""
    start = hlo.find("custom-call(")
    if start < 0:
        return []
    depth, i = 0, start + len("custom-call(")
    j = i
    while j < len(hlo):
        c = hlo[j]
        if c in "({[":
            depth += 1
        elif c in ")}]":
            if depth == 0:
                break
            depth -= 1
        j += 1
    out = []
    for part in re.split(r"\s%[\w\-.]+,?\s*", hlo[i:j] + " "):
        m = _SHAPE.search(part)
        if m:
            dims = tuple(int(x) for x in m.group(2).split(",") if x)
            out.append((m.group(1), dims))
    return out


def is_paged_attention(hlo: str) -> bool:
    """Whether an operation is the paged-attention kernel, told by its
    operands and not its name: a TPU custom call taking a page table
    ``s32[B, W]``, queries ``[B, KV, G, D]``, two page pools
    ``[KV, P, page, D]`` and valid lengths ``s32[B]``."""
    if 'custom_call_target="tpu_custom_call"' not in hlo:
        return False
    ops = operands(hlo)
    if len(ops) != 5:
        return False
    (t, tbl), (_, q), (_, k), (_, v), (l, vl) = ops
    return (t == "s32" and l == "s32" and len(tbl) == 2 and len(q) == 4
            and len(k) == 4 and k == v and len(vl) == 1
            and tbl[0] == q[0] == vl[0] and q[1] == k[0] and q[3] == k[3])


def op_key(hlo: str, module: str) -> str:
    """A readable, stable key for an operation: its program, its HLO
    instruction name without the numeric suffix, and its output shape."""
    head, _, rest = hlo.partition(" = ")
    instr = re.sub(r"\.\d+$", "", head.lstrip("%"))
    m = _SHAPE.search(rest)
    shape = f" {m.group(0)}" if m else ""
    return f"{module}/{instr}{shape}"


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def _label(t: float, spans: Dict[str, List[Tuple[float, float]]]) -> str:
    for name in HOST_SPANS:
        for a, b in spans.get(name, ()):
            if a <= t <= b:
                return name
    return "outside steps"


def reduce(path: str, *, top: int = 10) -> Dict:
    """Reduce one trace file.  Returns seconds throughout:

    * ``busy_s`` -- union of the operation intervals, averaged over the
      device planes;
    * ``extent_s`` -- first operation start to last end, averaged;
    * ``ops`` -- [[key, seconds]] of the ``top`` costliest operation keys;
    * ``kernel_s``, ``kernel_calls`` -- the paged-attention events;
    * ``spans`` -- {host span name: [seconds, count]};
    * ``idle_gaps`` -- [[label, seconds]] of the ``top`` longest gaps
      between operations, each labelled by the innermost benchmark span
      the host was in at the gap's middle.
    """
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host_spans = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines:
                devices.append(lines)
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                for e in ln.events:
                    if e.name in HOST_SPANS:
                        host_spans.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    busy = extent = kernel_ns = 0.0
    kernel_calls = 0
    per_op: Dict[str, float] = {}
    gaps: List[Tuple[float, str]] = []
    for lines in devices:
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       e.name.split("(")[0])
                      for e in lines["XLA Modules"].events) \
            if "XLA Modules" in lines else []
        starts = [m[0] for m in mods]
        ivs = []
        for e in lines["XLA Ops"].events:
            a, b = e.start_ns, e.start_ns + e.duration_ns
            ivs.append((a, b))
            i = bisect.bisect_right(starts, a) - 1
            mod = mods[i][2] if i >= 0 and a <= mods[i][1] else "?"
            key = op_key(e.name, mod)
            per_op[key] = per_op.get(key, 0.0) + e.duration_ns
            if is_paged_attention(e.name):
                kernel_ns += e.duration_ns
                kernel_calls += 1
        merged = union(ivs)
        busy += sum(b - a for a, b in merged)
        if merged:
            extent += merged[-1][1] - merged[0][0]
        for (_, b), (a, _) in zip(merged, merged[1:]):
            gaps.append((a - b, _label((a + b) / 2, host_spans)))
    n = max(len(devices), 1)
    gaps.sort(key=lambda g: -g[0])
    return {
        "devices": len(devices),
        "busy_s": busy / n / 1e9,
        "extent_s": extent / n / 1e9,
        "ops": [[k, v / n / 1e9] for k, v in
                sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        "kernel_s": kernel_ns / n / 1e9,
        "kernel_calls": kernel_calls // n,
        "spans": {k: [sum(b - a for a, b in v) / 1e9, len(v)]
                  for k, v in host_spans.items()},
        "idle_gaps": [[label, g / 1e9] for g, label in gaps[:top]],
    }


def find_trace(root: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under ``root``."""
    import glob
    import os
    found = glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None
