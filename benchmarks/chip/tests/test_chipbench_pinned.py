"""The device metrics that count work, read from records that traced chip
runs of each cell left, against the values the harness read before the
work counts moved into the architecture modules.

Each fixture under ``fixtures/records/`` holds a run's ``model`` and
``peaks`` and the measured part of its ``trace`` section (device times,
the traced decode steps' lanes and the prefills), from one traced run of
the cell on a v5e at seed 3405691582.  The run adds the work the cell's
architecture module counts; the readers then have to give what the
previous harness gave on the same record, which counted the work in the
readers themselves.  ``model`` is what the previous harness recorded, so
the configuration file has to map to it through the module too."""

import json
import os

import pytest

import chipbench_setup  # noqa: F401  (the benchmark and src on the path)
import registry
import run

from repro.configs.base import ModelConfig

RECORDS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "records")

#: the previous harness's readings of each record
PINNED = {
    "nemo-chat-shared": {
        "decode_mfu": 1.8680923410203727,
        "prefill_mfu": 17.198961736465787,
        "paged_attn_roofline": 9.21153939820296,
    },
    "phi3-longgen": {
        "decode_mfu": 0.4527769494632028,
        "prefill_mfu": 50.60844186916014,
        "paged_attn_roofline": 8.25018685514154,
    },
}


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_readings_of_a_chip_record_are_pinned(cell):
    with open(os.path.join(RECORDS, cell + ".json")) as f:
        record = json.load(f)
    config = registry.load_cell(cell).config
    arch = registry.arch(config)
    cfg = ModelConfig(**arch.model_config_kwargs(config))
    assert arch.numbers(cfg) == record["model"]
    run.add_work(record["trace"], arch, record["model"])
    for name, want in PINNED[cell].items():
        got = registry.metric_reader(name)(record)
        assert got == pytest.approx(want, rel=1e-12, abs=0), name
