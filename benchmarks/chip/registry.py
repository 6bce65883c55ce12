"""Find what a cell is made of, by name, from files alone.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix;
the benchmark's per-layer metrics are named there too.  Each lives in a
file of its own under the benchmark directory, so a later change adds a
cell, a mix, a metric or an architecture by adding files and entries,
never by editing code:

* ``configs/<config>.json`` -- the model as it is run (Hugging Face
  ``config.json`` keys) and the serve options of the deployment;
* ``arch/<model_type>.py`` -- what is particular to the configuration's
  architecture: the program's configuration, the plain reference and
  the work counts;
* ``traffic/<mix>.json`` -- the parameters of one traffic mix, whose
  ``kind`` names the generator module ``traffic/<kind>.py``;
* ``metrics/<metric>.py`` -- the reader of one per-layer metric, a
  function ``read(record)`` that returns a number or None.

Nothing here imports JAX or the program (an architecture module may).
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

#: the benchmark's own directory (configs/, arch/, traffic/, metrics/ here)
HERE = os.path.dirname(os.path.abspath(__file__))
#: the checkout's root, where BENCHMARK.json lives
ROOT = os.path.dirname(os.path.dirname(HERE))


@dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, *, root: str = ROOT,
              bench_dir: Optional[str] = None) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its
    configuration and traffic files read from ``bench_dir`` (the
    benchmark's directory unless given) and its metrics filtered to
    those the cell reports."""
    bench_dir = bench_dir or HERE
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[w["config"]]
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     w["traffic"] + ".json"))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, name)])


def _load_module(path: str, mod_name: str):
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generator(kind: str, *, bench_dir: Optional[str] = None):
    """The traffic generator module ``traffic/<kind>.py``."""
    bench_dir = bench_dir or HERE
    return _load_module(os.path.join(bench_dir, "traffic", kind + ".py"),
                        f"chipbench_traffic_{kind}")


def metric_reader(name: str, *, bench_dir: Optional[str] = None
                  ) -> Callable[[Dict[str, Any]], Optional[float]]:
    """``read(record)`` of ``metrics/<name>.py``."""
    bench_dir = bench_dir or HERE
    mod = _load_module(os.path.join(bench_dir, "metrics", name + ".py"),
                       "chipbench_metric_" + name.replace(".", "_")
                       .replace("-", "_"))
    return mod.read


def arch(config: Dict[str, Any], *, bench_dir: Optional[str] = None):
    """The architecture module ``arch/<model_type>.py`` that the
    configuration names by its own ``model_type`` key: the program's
    configuration for it, its plain reference and its work counts
    (``arch/__init__.py`` lists what each module gives)."""
    bench_dir = bench_dir or HERE
    kind = str(config.get("model_type"))
    path = os.path.join(bench_dir, "arch", kind + ".py")
    if not os.path.isfile(path):
        raise KeyError(f"{config.get('name')}: no architecture module "
                       f"{path} for model_type {kind!r}")
    return _load_module(path, "chipbench_arch_" + kind.replace(".", "_")
                        .replace("-", "_"))
