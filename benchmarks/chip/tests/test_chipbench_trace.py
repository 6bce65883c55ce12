"""``trace_reduce`` on a small recorded trace.

The fixture is a profile of one v5e chip running a jitted step three
times: the paged-attention kernel over a (16 lanes, 32 KV heads, 16-page
table) call at head_dim 96, the pool's two relayout copies and a small
matmul, each step inside a host ``TraceAnnotation``.  The device ran
three programs of 3.889676, 3.887399 and 3.888811 ms; the kernel took
about 3.03 ms of each, each relayout copy about 0.43 ms."""

import os

import pytest

import chipbench_setup  # noqa: F401  (the benchmark and src on the path)
import trace_reduce

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "paged_probe.xplane.pb")
KERNEL_HLO = (
    "%step.1 = bf16[16,32,1,96]{3,2,1,0} custom-call(s32[16,16]{1,0} "
    "%copy-done.2, bf16[16,32,1,96]{3,2,1,0} %copy.4, "
    "bf16[32,151,128,96]{3,2,1,0} %bitcast.1, bf16[32,151,128,96]{3,2,1,0}"
    " %bitcast.2, s32[16]{0} %copy-done.3), "
    'custom_call_target="tpu_custom_call", operand_layout_constraints={}')


@pytest.fixture(scope="module")
def reduced():
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
    assert ops["jit_step/step bf16[16,32,1,96]"] == reduced["kernel_s"]
    # the two relayout copies of the pool, three times
    assert ops["jit_step/copy bf16[151,128,32,96]"] == pytest.approx(
        0.002574597, abs=1e-8)
    assert [k for k, _ in reduced["ops"]][:2] == [
        "jit_step/step bf16[16,32,1,96]",
        "jit_step/copy bf16[151,128,32,96]"]
    assert sum(v for _, v in reduced["ops"]) <= reduced["busy_s"] + 1e-9


def test_kernel_told_by_its_operands():
    assert trace_reduce.operands(KERNEL_HLO) == [
        ("s32", (16, 16)), ("bf16", (16, 32, 1, 96)),
        ("bf16", (32, 151, 128, 96)), ("bf16", (32, 151, 128, 96)),
        ("s32", (16,))]
    assert trace_reduce.is_paged_attention(KERNEL_HLO)
    other = KERNEL_HLO.replace("s32[16]{0} %copy-done.3",
                               "s32[8]{0} %copy-done.3")
    assert not trace_reduce.is_paged_attention(other)
    assert not trace_reduce.is_paged_attention(
        "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop")


def test_union_and_labels():
    assert trace_reduce.union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [
        (0, 3), (5, 7)]
    spans = {"chipbench.step": [(0, 100)], "chipbench.decode": [(10, 20)]}
    assert trace_reduce._label(15, spans) == "chipbench.decode"
    assert trace_reduce._label(50, spans) == "chipbench.step"
    assert trace_reduce._label(150, spans) == "outside steps"
