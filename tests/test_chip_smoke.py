"""``chip_smoke.py`` on the CPU: its phases at ``reduced_config`` size with
the same checks, and its refusal to report anything off a TPU.

Phase A runs with ``ops.paged_attention`` replaced by the Pallas kernel
under the interpreter, so the serving path and the smoke's kernel check
both go through the kernel as they do on the chip.  The compiled decode
program's kernel is checked on the chip and in tests/test_tpu_compile.py."""

import functools
import importlib.util
import json
import os

import pytest

from repro.configs import get_config
from repro.configs.reduced import reduced_config
from repro.core.materializer import MeshSpec
from repro.kernels import ops
from repro.kernels.paged_attention import paged_attention

_PATH = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg():
    return reduced_config(get_config("tinyllama-1.1b"))


@pytest.fixture(scope="module")
def mesh():
    """One described v5e: the phases admit against its HBM."""
    return MeshSpec.of_chip("one", (1, 1), ("data", "model"), "TPU v5 lite")


def test_phase_a_plain_paged_serving(smoke, cfg, mesh, monkeypatch):
    monkeypatch.setattr(ops, "paged_attention",
                        functools.partial(paged_attention, interpret=True))
    out = smoke.phase_a(cfg, mesh)
    assert out["completed"] == 8
    assert out["tokens"] == 8 * smoke.NEW_TOKENS
    # one native prefill shape + two chunk shapes; two decode widths
    assert out["prefill_traces"] == 3 and out["decode_traces"] == 2
    assert out["kernel_max_abs_err"] <= smoke.KERNEL_TOL["atol"]


def test_phase_b_prefix_replicas_park(smoke, cfg, mesh):
    out = smoke.phase_b(cfg, mesh)
    assert out["completed"] == 12
    assert out["replicas"] == 2
    assert out["prefix_hit_rate"] > 0
    assert out["park_freed_pages"] > 0 and out["park_drained"] > 0


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main() != 0
    captured = capsys.readouterr()
    assert "needs a TPU" in captured.err
    for line in captured.out.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
