"""``trace_reduce`` on a small recorded trace.

The fixture is a profile of one v5e chip running a jitted step three
times: the paged-attention kernel over a (16 lanes, 32 KV heads, 16-page
table) call at head_dim 96, the pool's two relayout copies and a small
matmul, each step inside a host ``TraceAnnotation``.  The device ran
three programs of 3.889676, 3.887399 and 3.888811 ms; the kernel took
about 3.03 ms of each, each relayout copy about 0.43 ms.  The kernel's
event metadata gives its source line as ``paged_attention.py:160``
(``LINE``): that line of the kernel module as it stood then lay in
``paged_attention``, the function that made the ``pallas_call``.  The
module has changed since, so the fixture's tests have
``trace_reduce.function_at`` read the recorded file as a stand-in
(``module_when_recorded``) that holds its functions at the lines they
held then."""

import os

import pytest

from chipbench_setup import REPO
import trace_reduce

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "paged_probe.xplane.pb")
LINE = "/src/repro/kernels/paged_attention.py:160"
KERNELS = os.path.join(REPO, "src", "repro", "kernels",
                       "paged_attention.py")


#: the functions of the kernel module when the fixture was recorded,
#: (first line, last line, name), and the line of its ``pallas_call``
FUNCTIONS_WHEN_RECORDED = ((65, 108, "_paged_kernel"),
                           (111, 167, "paged_attention"),
                           (170, 196, "paged_attention_ref"))


def module_when_recorded(path):
    """Write at ``path`` a stand-in for the kernel module as the fixture's
    run read it: its functions at the lines they held then, the
    ``pallas_call`` on ``LINE``'s line."""
    lines = ["# kernel module"] * FUNCTIONS_WHEN_RECORDED[-1][1]
    for first, last, name in FUNCTIONS_WHEN_RECORDED:
        lines[first - 1] = f"def {name}():"
        lines[first:last] = ["    pass"] * (last - first)
    lines[int(LINE.rpartition(":")[2]) - 1] = "    out = pl.pallas_call(k)"
    path.write_text("\n".join(lines) + "\n")


def reading(stand_in):
    """``trace_reduce.function_at`` with the fixture's recorded file read
    as ``stand_in``."""
    real = trace_reduce.function_at
    recorded = LINE.rpartition(":")[0]

    def at(source):
        path, _, line = source.rpartition(":")
        return real(f"{stand_in}:{line}" if path.endswith(recorded)
                    else source)
    return at


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    stand_in = tmp_path_factory.mktemp("kernels") / "paged_attention.py"
    module_when_recorded(stand_in)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(trace_reduce, "function_at", reading(stand_in))
        return trace_reduce.reduce(FIXTURE)


def test_busy_is_the_union_of_operations(reduced):
    modules_s = (3889676 + 3887399 + 3888811) * 1e-9
    assert reduced["devices"] == 1
    # every operation runs inside a program: busy is at most the
    # programs' time, and the gaps inside programs are a few microseconds
    assert reduced["busy_s"] == pytest.approx(0.01166501, abs=1e-8)
    assert reduced["busy_s"] <= modules_s
    assert modules_s - reduced["busy_s"] < 2e-6
    # three steps 15-16 ms apart: two idle gaps of about 12 ms between
    # them, the longest listed first
    (l1, g1), (l2, g2) = reduced["idle_gaps"][:2]
    assert g1 == pytest.approx(0.012067764, abs=1e-8)
    assert g2 == pytest.approx(0.011508184, abs=1e-8)
    assert l1 == l2 == "outside steps"
    assert reduced["extent_s"] == pytest.approx(
        reduced["busy_s"] + sum(g for _, g in reduced["idle_gaps"]),
        abs=1e-7)


def test_per_operation_time_and_the_kernel(reduced):
    ops = dict(reduced["ops"])
    assert reduced["kernel_calls"] == 3
    assert reduced["kernel_s"] == pytest.approx(0.009082721, abs=1e-8)
    assert reduced["kernels"] == {
        "paged_attention": [reduced["kernel_s"], 3]}
    assert ops["jit_step/step bf16[16,32,1,96]"] == reduced["kernel_s"]
    # the two relayout copies of the pool, three times
    assert ops["jit_step/copy bf16[151,128,32,96]"] == pytest.approx(
        0.002574597, abs=1e-8)
    assert [k for k, _ in reduced["ops"]][:2] == [
        "jit_step/step bf16[16,32,1,96]",
        "jit_step/copy bf16[151,128,32,96]"]
    assert sum(v for _, v in reduced["ops"]) <= reduced["busy_s"] + 1e-9


def test_kernel_told_by_its_name(tmp_path, monkeypatch):
    """Each TPU custom call is named by the function whose body holds its
    source line: in the fixture, the one kernel call and its line; in
    today's kernel module, each ``pallas_call`` line, one of them in
    ``paged_attention`` (the name ``paged_attn_roofline`` reads)."""
    (hlo, source), = trace_reduce.custom_call_sources(FIXTURE).items()
    assert source.endswith(LINE)
    assert trace_reduce.TPU_CUSTOM_CALL in hlo
    with open(KERNELS) as f:
        lines = f.read().splitlines()
    calls = [i + 1 for i, ln in enumerate(lines) if "pl.pallas_call(" in ln]
    names = [trace_reduce.function_at(f"{KERNELS}:{n}") for n in calls]
    assert None not in names and "paged_attention" in names
    # the innermost function; none outside functions or without the file
    src = tmp_path / "k.py"
    src.write_text("x = 1\n"
                   "def outer():\n"
                   "    def inner():\n"
                   "        return pl.pallas_call(k)\n"
                   "    return inner\n")
    assert trace_reduce.function_at(f"{src}:4") == "inner"
    assert trace_reduce.function_at(f"{src}:5") == "outer"
    assert trace_reduce.function_at(f"{src}:1") is None
    assert trace_reduce.function_at(f"{tmp_path}/gone.py:4") is None
    # the stand-in names the recorded line as the recording run's file did
    stand_in = tmp_path / "paged_attention.py"
    module_when_recorded(stand_in)
    assert reading(stand_in)(source) == "paged_attention"
    # a call whose file cannot be read keeps its source as its name
    monkeypatch.setattr(trace_reduce, "function_at",
                        reading(tmp_path / "gone.py"))
    got = trace_reduce.reduce(FIXTURE)
    assert got["kernels"] == {
        source: [pytest.approx(0.009082721, abs=1e-8), 3]}
    assert got["kernel_s"] == 0.0 and got["kernel_calls"] == 0


def test_union_and_labels():
    assert trace_reduce.union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [
        (0, 3), (5, 7)]
    spans = {"chipbench.step": [(0, 100)], "chipbench.decode": [(10, 20)]}
    assert trace_reduce._label(15, spans) == "chipbench.decode"
    assert trace_reduce._label(50, spans) == "chipbench.step"
    assert trace_reduce._label(150, spans) == "outside steps"
