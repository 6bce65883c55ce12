"""Set-up of the chip benchmark's own tests, imported by each of them (a
module of its own, not a ``conftest.py``: the repository's tests import
names from theirs, and a second one would shadow it).  It puts the
benchmark's directory and ``src`` on the path, and gives the fixture
``checkout``: a copy of the benchmark in a temporary checkout, with a
tiny configuration and tiny traffic mixes added as files, the way a
later change adds a cell."""

import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(os.path.dirname(BENCH))
for p in (BENCH, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: a tiny dense model: every width of the served architecture, shrunk
TINY = {
    "name": "tiny", "model_type": "mistral", "hidden_act": "silu",
    "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 512, "max_position_embeddings": 4096,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False,
    "serve": {"max_batch": 4, "pool_pages": 64, "prefix_cache": True,
              "chunk_pages": None},
    "correct": {"max_logit_gap": 0.005},
}


def add_cell(root, name, config, traffic, traffic_params, like):
    """Register a cell in ``root``'s BENCHMARK.json with new files only;
    it reports the metrics the cell ``like`` reports."""
    bench = os.path.join(root, "benchmarks", "chip")
    with open(os.path.join(bench, "configs", config["name"] + ".json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "traffic", traffic + ".json"), "w") as f:
        json.dump(traffic_params, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    if config["name"] not in {c["name"] for c in spec["configs"]}:
        spec["configs"].append({
            "name": config["name"], "source": "test", "reduced": [],
            "file": f"benchmarks/chip/configs/{config['name']}.json",
            "why": "test"})
    spec["workloads"].append({"name": name, "config": config["name"],
                              "traffic": traffic, "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(name)
    with open(path, "w") as f:
        json.dump(spec, f)


@pytest.fixture
def checkout(tmp_path):
    """A checkout holding BENCHMARK.json and the benchmark's files, with
    tiny cells ``tiny-chat`` and ``tiny-long`` added as files."""
    root = str(tmp_path)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, os.path.join(root, "benchmarks", "chip"),
                    ignore=shutil.ignore_patterns(
                        ".jax_cache", "out", "tests", "__pycache__"))
    with open(os.path.join(BENCH, "traffic", "chat-shared.json")) as f:
        chat = json.load(f)
    chat.update({"session_rate_per_s": 2.0, "system_prompts": [256, 384],
                 "user_len": {"median": 64, "sigma": 1.0, "min": 16,
                              "max": 256},
                 "output_len": {"median": 24, "sigma": 0.7, "min": 8,
                                "max": 48},
                 "max_context": 1024, "think_mean_s": 0.2})
    add_cell(root, "tiny-chat", TINY, "tiny-chat", chat,
             "nemo-chat-shared")
    with open(os.path.join(BENCH, "traffic", "longgen-closed.json")) as f:
        long_ = json.load(f)
    long_.update({"prompt_len": [16, 64], "output_len": [16, 48],
                  "max_total": 128})
    add_cell(root, "tiny-long", TINY, "tiny-long", long_,
             "phi3-longgen")
    return root
