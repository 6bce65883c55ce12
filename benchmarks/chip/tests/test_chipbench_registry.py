"""The harness finds a configuration, a traffic mix, a generator and a
metric added as new files, with no edit to a file already there."""

import json
import os
import re

import pytest

from chipbench_setup import checkout  # noqa: F401  (a fixture)
import registry


def test_new_files_are_found_by_name(checkout):
    bench = os.path.join(checkout, "benchmarks", "chip")
    with open(os.path.join(bench, "metrics", "served_requests.py"),
              "w") as f:
        f.write("def read(record):\n"
                "    return float(len(record['requests']))\n")
    with open(os.path.join(bench, "traffic", "one_shot.py"), "w") as f:
        f.write("class S:\n"
                "    def __init__(self, p, seed):\n"
                "        self.n = p['n'] + seed\n"
                "def make(params, *, seed, vocab, seconds, max_batch):\n"
                "    return S(params, seed)\n")
    with open(os.path.join(bench, "traffic", "burst.json"), "w") as f:
        json.dump({"kind": "one_shot", "n": 3}, f)
    path = os.path.join(checkout, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["workloads"].append({"name": "tiny-burst", "config": "tiny",
                              "traffic": "burst", "chips": 1, "why": "t"})
    spec["per_layer"].append({
        "name": "served_requests", "unit": "requests", "better": "higher",
        "source": "host_clock", "layer": "engine", "moves": "output_tok_s",
        "workloads": ["tiny-burst"]})
    with open(path, "w") as f:
        json.dump(spec, f)

    cell = registry.load_cell("tiny-burst", root=checkout, bench_dir=bench)
    assert cell.config["hidden_size"] == 64
    assert cell.traffic == {"kind": "one_shot", "n": 3}
    # metrics without a workloads list apply to every cell
    assert {m["name"] for m in cell.end_to_end} == {
        "itl_p95_ms", "output_tok_s", "peak_hbm_gb", "setup_s"}
    assert [m["name"] for m in cell.per_layer] == ["served_requests"]
    src = registry.generator("one_shot", bench_dir=bench).make(
        cell.traffic, seed=4, vocab=10, seconds=1.0, max_batch=2)
    assert src.n == 7
    read = registry.metric_reader("served_requests", bench_dir=bench)
    assert read({"requests": [1, 2]}) == 2.0


def test_config_file_maps_to_the_program_config(checkout):
    bench = os.path.join(checkout, "benchmarks", "chip")
    cell = registry.load_cell("tiny-chat", root=checkout, bench_dir=bench)
    dense = registry.arch(cell.config, bench_dir=bench)
    kw = dense.model_config_kwargs(cell.config)
    assert kw["num_kv_heads"] == 2 and kw["head_dim"] == 16
    assert kw["norm_eps"] == 1e-5 and kw["family"] == "dense"
    bad = dict(cell.config, hidden_act="gelu")
    with pytest.raises(ValueError, match="gelu"):
        dense.model_config_kwargs(bad)


def test_model_type_without_a_module_is_refused(checkout):
    """A configuration whose ``model_type`` has no ``arch/`` module is
    refused, naming the file it lacks; it never runs as the dense
    decoder, and neither does one that names no type."""
    bench = os.path.join(checkout, "benchmarks", "chip")
    cell = registry.load_cell("tiny-chat", root=checkout, bench_dir=bench)
    latent = dict(cell.config, model_type="deepseek_v2")
    want = os.path.join(bench, "arch", "deepseek_v2.py")
    with pytest.raises(KeyError, match=re.escape(want)):
        registry.arch(latent, bench_dir=bench)
    untyped = {k: v for k, v in cell.config.items() if k != "model_type"}
    with pytest.raises(KeyError, match="None.py"):
        registry.arch(untyped, bench_dir=bench)
