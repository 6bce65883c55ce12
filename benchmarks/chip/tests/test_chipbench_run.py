"""``run.py`` end to end on the CPU at a tiny size, with the look for a
chip skipped: a sound run is correct, compiles nothing in its window, and
the float8 control put in the program's place is not correct; a run
whose tokens are altered where they are produced is not correct;
without a TPU the command exits non-zero and prints no result; and an
architecture added as new files alone runs end to end."""

import hashlib
import json
import os
import subprocess
import sys

import pytest

from chipbench_setup import REPO, checkout  # noqa: F401  (a fixture)
import driver
import registry
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
    kw = registry.arch(cell.config).model_config_kwargs(cell.config)
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


#: a throwaway architecture: the program's dense decoder under keys of its
#: own, its reference taken from the dense module, work counts of its own
#: and one kernel of its own
TOY_ARCH = """
from arch.dense import served_gaps, weights  # noqa: F401


def model_config_kwargs(config):
    return dict(name=config["name"], family="dense",
                num_layers=config["n_layer"], d_model=config["n_embd"],
                num_heads=config["n_head"], num_kv_heads=config["n_kv"],
                head_dim=config["n_embd"] // config["n_head"],
                d_ff=config["n_inner"], vocab_size=config["n_vocab"],
                rope_theta=10000.0, norm_eps=1e-5, tie_embeddings=False,
                max_context=4096)


def numbers(cfg):
    return {"layers": cfg.num_layers, "d_model": cfg.d_model,
            "heads": cfg.num_heads, "kv_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "d_ff": cfg.d_ff,
            "vocab": cfg.vocab_size, "norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta}


def decode_flops(m, lens):
    return 7 * len(lens)


def prefill_flops(m, cached, total):
    return 7 * (total - cached)


def toy_attention(m, lens):
    return sum(lens), 3 * m["layers"] * sum(lens)


KERNELS = {"toy_attention": toy_attention}
"""

TOY_METRIC = """
def read(record):
    tr = record.get("trace")
    return None if not tr else float(tr["kernel_work"]["toy_attention"][1])
"""


def tree_hashes(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_architecture_added_as_files_alone(checkout, capsys,  # noqa: F811
                                           monkeypatch, tmp_path):
    """``arch/toy.py``, ``configs/toy.json``, a metric that reads the toy
    kernel's work and entries in ``BENCHMARK.json``: a traced run of the
    cell goes through the toy module for the program's configuration,
    the reference and the work, and no file already there changes."""
    bench = os.path.join(checkout, "benchmarks", "chip")
    before = tree_hashes(checkout)
    spec_path = os.path.join(checkout, "BENCHMARK.json")
    with open(spec_path) as f:
        old = json.load(f)
    with open(os.path.join(bench, "arch", "toy.py"), "w") as f:
        f.write(TOY_ARCH)
    with open(os.path.join(bench, "metrics", "toy_attention_bytes.py"),
              "w") as f:
        f.write(TOY_METRIC)
    toy = {"name": "toy", "model_type": "toy", "n_embd": 64, "n_inner": 128,
           "n_head": 4, "n_kv": 2, "n_layer": 2, "n_vocab": 512,
           "serve": {"max_batch": 4, "pool_pages": 64,
                     "prefix_cache": False, "chunk_pages": None},
           "correct": {"max_logit_gap": 0.005}}
    with open(os.path.join(bench, "configs", "toy.json"), "w") as f:
        json.dump(toy, f)
    spec = json.loads(json.dumps(old))
    spec["configs"].append({"name": "toy", "source": "test", "reduced": [],
                            "file": "benchmarks/chip/configs/toy.json",
                            "why": "test"})
    spec["workloads"].append({"name": "toy-long", "config": "toy",
                              "traffic": "tiny-long", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({
        "name": "toy_attention_bytes", "unit": "bytes", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "itl_p95_ms", "workloads": ["toy-long"]})
    with open(spec_path, "w") as f:
        json.dump(spec, f)

    used = []
    real = registry.arch

    def arch(config, **kw):
        mod = real(config, **kw)
        gaps = mod.served_gaps

        def counted(*a, **k):
            used.append(mod.__file__)
            return gaps(*a, **k)
        mod.served_gaps = counted
        return mod

    monkeypatch.setattr(registry, "arch", arch)
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path / "trace"))
    rc = run.main(["--workload", "toy-long", "--seed", str(2**31 + 9),
                   "--seconds", "4", "--trace", "1"], require_tpu=False,
                  mesh=MESH, root=checkout, bench_dir=bench,
                  persistent_cache=False)
    assert rc == 0
    r = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert r["correct"], r["checks"]
    assert r["checks"]["tokens_compared"]["value"] >= 64
    assert set(used) == {os.path.join(bench, "arch", "toy.py")}
    # the toy kernel's bytes: 3 x 2 layers x the live tokens of every
    # traced decode step, a multiple of 6 and not 0
    v = r["metrics"]["toy_attention_bytes"]["value"]
    assert set(r["metrics"]) == {"toy_attention_bytes"}
    assert v > 0 and v % 6 == 0
    after = tree_hashes(checkout)
    changed = {p for p in before if after.get(p) != before[p]}
    assert changed == {"BENCHMARK.json"}
    with open(spec_path) as f:
        now = json.load(f)
    for key, entries in old.items():
        assert now[key][:len(entries)] == entries if isinstance(
            entries, list) else now[key] == entries
