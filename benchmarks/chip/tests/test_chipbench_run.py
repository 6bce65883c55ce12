"""``run.py`` end to end on the CPU at a tiny size, with the look for a
chip skipped: a sound run is correct, compiles nothing in its window, and
the float8 control put in the program's place is not correct; a run
whose tokens are altered where they are produced is not correct; and
without a TPU the command exits non-zero and prints no result."""

import json
import os
import subprocess
import sys

import pytest

from chipbench_setup import REPO, checkout  # noqa: F401  (a fixture)
import driver
import run

from repro.core.materializer import MeshSpec

MESH = MeshSpec.of_chip("test", (1, 1), ("data", "model"), "TPU v5 lite")


def run_cell(checkout, capsys, workload, seed, *extra):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "5", "--trace", "0", *extra],
                  require_tpu=False, mesh=MESH, root=checkout,
                  bench_dir=os.path.join(checkout, "benchmarks", "chip"),
                  persistent_cache=False)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    result = json.loads(out[-1])
    assert list(result)[-1] == "checks"
    return result


def test_sound_run_is_correct_and_the_control_is_not(checkout, capsys):
    r = run_cell(checkout, capsys, "tiny-chat", 2**31 + 77)
    c = r["checks"]
    assert r["correct"], c
    assert r["attempted"] > 0 and r["failed"] == 0
    assert c["tokens_compared"]["value"] >= c["tokens_compared"]["limit"]
    assert c["max_logit_gap"]["value"] <= c["max_logit_gap"]["limit"]
    assert c["compiles_in_window"]["value"] == 0
    assert set(r["metrics"]) == {"ttft_p85_ms", "itl_p95_ms",
                                 "output_tok_s", "setup_s"}
    r = run_cell(checkout, capsys, "tiny-chat", 2**31 + 77, "--control",
                 "1")
    c = r["checks"]
    assert not r["correct"], c
    assert c["max_logit_gap"]["value"] > c["max_logit_gap"]["limit"]
    assert c["tokens_compared"]["value"] >= c["tokens_compared"]["limit"]


def test_closed_loop_run_is_correct(checkout, capsys):
    r = run_cell(checkout, capsys, "tiny-long", 5)
    assert r["correct"], r["checks"]
    assert r["checks"]["compiles_in_window"]["value"] == 0
    assert "ttft_p85_ms" not in r["metrics"]


def test_token_altered_where_produced_is_not_correct(checkout, capsys,
                                                     monkeypatch):
    """Every fourth decode step, the newest token of every running
    request is replaced by the next id, as a faulty sampler would."""
    wrap = driver.Run._wrap_runner

    def faulty(self):
        wrap(self)
        decode = self.runner.decode
        calls = [0]

        def altered(running):
            decode(running)
            calls[0] += 1
            if calls[0] % 4 == 0:
                for r in running:
                    toks = self.runner.generated[r.req_id]
                    toks[-1] = (toks[-1] + 1) % self.runner.cfg.vocab_size

        self.runner.decode = altered
        for rep in self.handle.replica_set.replicas:
            rep.engine.step_fns = (self.runner.prefill, altered)

    monkeypatch.setattr(driver.Run, "_wrap_runner", faulty)
    r = run_cell(checkout, capsys, "tiny-chat", 2**31 + 77)
    assert not r["correct"]
    assert r["checks"]["max_logit_gap"]["value"] > \
        r["checks"]["max_logit_gap"]["limit"]


def test_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "nemo-chat-shared", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


@pytest.mark.parametrize("name", ["nemo-chat-shared", "phi3-longgen"])
def test_cells_of_the_benchmark_load(name):
    import registry
    cell = registry.load_cell(name)
    assert cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s",
                                                    "itl_p95_ms"}
    for m in cell.end_to_end + cell.per_layer:
        assert callable(registry.metric_reader(m["name"]))
    kw = registry.model_config_kwargs(cell.config)
    assert kw["num_layers"] >= 10 and kw["d_model"] >= 3072


def test_shape_left_cold_by_the_warm_up_is_not_correct(checkout, capsys,
                                                      monkeypatch):
    """A warm-up that serves none of the traffic's prompt lengths leaves
    their prompt operations to compile in the window, and the run says
    so.  The process's compiled programs are dropped first, as a run
    starts without them."""
    import jax
    jax.clear_caches()
    monkeypatch.setattr(driver, "warm_plan", lambda *a: ([], []))
    r = run_cell(checkout, capsys, "tiny-long", 6)
    assert not r["correct"]
    assert r["checks"]["compiles_in_window"]["value"] > 0
