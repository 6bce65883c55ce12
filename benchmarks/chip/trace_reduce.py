"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time, time per device operation, each kernel's
events by the kernel's name, the host spans the benchmark placed, and
the longest idle gaps labelled by what the host was doing in them.

Times come from JAX's own reader (``jax.profiler.ProfileData``).  A
device plane is one named ``/device:TPU:<n>``; its ``XLA Ops`` line
holds one event per operation, named by the operation's HLO text, and
its ``XLA Modules`` line one event per program run.  The host plane
``/host:CPU`` holds the ``jax.profiler.TraceAnnotation`` spans
(``chipbench.*``).  Times in the trace are nanoseconds.

A kernel (a Pallas ``pallas_call``, a TPU custom call in the HLO) is
told by the name of the Python function whose body makes the
``pallas_call`` (``paged_attention``, ``kv_write``): the trace keeps
each operation's source line (the ``source`` stat of its event
metadata, ``<file>:<line>``), and that file, read in the run that wrote
the trace, says which function holds the line.  ``ProfileData`` does
not give an event's metadata, so ``custom_call_sources`` reads it from
the trace's protobuf.
"""

from __future__ import annotations

import ast
import bisect
import re
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

#: host spans the harness places, innermost first (idle-gap labels)
HOST_SPANS = ("chipbench.prefill", "chipbench.decode", "chipbench.step")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
#: what the HLO text of a Pallas kernel's operation holds
TPU_CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'
#: the kernel whose events are ``kernel_s`` and ``kernel_calls``
PAGED_ATTENTION = "paged_attention"


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of each field of one serialized protobuf
    message: an int for a varint, a memoryview for a length-delimited
    field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def custom_call_sources(path: str) -> Dict[str, str]:
    """{HLO text: source} of the TPU custom calls on the device planes of
    one trace.  The trace is an ``XSpace`` (planes: field 1); an
    ``XPlane`` has its name (2), its event metadata (4: a map from id to
    ``XEventMetadata``, whose name (2) is the HLO text and whose stats
    are field 5) and its stat metadata (5: a map from id to
    ``XStatMetadata``, named by field 2).  An ``XStat`` names its stat by
    field 1 and holds a string in field 5, or in field 7 as the id of
    the stat metadata whose name is the string."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out: Dict[str, str] = {}
    for num, plane in _fields(data):
        if num != 1:
            continue
        fields = list(_fields(plane))
        name = next((_text(v) for k, v in fields if k == 2), "")
        if not name.startswith("/device:TPU:"):
            continue
        stat_names = {}
        for k, v in fields:
            if k == 5:
                meta = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_names[meta.get(1)] = _text(meta.get(2, b""))
        for k, v in fields:
            if k != 4:
                continue
            meta = list(_fields(dict(_fields(v)).get(2, b"")))
            hlo = next((_text(x) for j, x in meta if j == 2), "")
            if TPU_CUSTOM_CALL not in hlo:
                continue
            for j, x in meta:
                if j != 5:
                    continue
                stat = dict(_fields(x))
                if stat_names.get(stat.get(1)) != "source":
                    continue
                out[hlo] = (_text(stat[5]) if 5 in stat
                            else stat_names.get(stat.get(7), ""))
    return out


def function_at(source: str) -> Optional[str]:
    """The name of the innermost function whose lines hold ``source``
    (``<file>:<line>``), or None where the file cannot be read or no
    function holds the line."""
    path, _, line = source.rpartition(":")
    if not line.isdigit():
        return None
    try:
        with open(path) as f:
            tree = ast.parse(f.read())
    except (OSError, SyntaxError, ValueError):
        return None
    n, best = int(line), None
    for node in ast.walk(tree):
        if (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and node.lineno <= n <= node.end_lineno
                and (best is None or node.lineno > best.lineno)):
            best = node
    return None if best is None else best.name


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
    * ``kernels`` -- {kernel name: [seconds, calls]} of every TPU custom
      call, named by ``function_at`` its source line (by the source line
      itself where that gives no name, ``unnamed`` where there is none);
    * ``kernel_s``, ``kernel_calls`` -- the ``paged_attention`` entry;
    * ``spans`` -- {host span name: [seconds, count]};
    * ``idle_gaps`` -- [[label, seconds]] of the ``top`` longest gaps
      between operations, each labelled by the innermost benchmark span
      the host was in at the gap's middle.
    """
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    sources = custom_call_sources(path)
    named = {src: function_at(src) or src for src in set(sources.values())}
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
    busy = extent = 0.0
    kernels: Dict[str, List] = {}
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
            if TPU_CUSTOM_CALL in e.name:
                k = kernels.setdefault(
                    named.get(sources.get(e.name), "unnamed"), [0.0, 0])
                k[0] += e.duration_ns
                k[1] += 1
        merged = union(ivs)
        busy += sum(b - a for a, b in merged)
        if merged:
            extent += merged[-1][1] - merged[0][0]
        for (_, b), (a, _) in zip(merged, merged[1:]):
            gaps.append((a - b, _label((a + b) / 2, host_spans)))
    n = max(len(devices), 1)
    gaps.sort(key=lambda g: -g[0])
    per_kernel = {k: [ns / n / 1e9, calls // n]
                  for k, (ns, calls) in kernels.items()}
    kernel_s, kernel_calls = per_kernel.get(PAGED_ATTENTION, [0.0, 0])
    return {
        "devices": len(devices),
        "busy_s": busy / n / 1e9,
        "extent_s": extent / n / 1e9,
        "ops": [[k, v / n / 1e9] for k, v in
                sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        "kernels": per_kernel,
        "kernel_s": kernel_s,
        "kernel_calls": kernel_calls,
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
