"""The harness finds what a later change adds as files, by name."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from chipbench import harness

BENCH = Path(__file__).resolve().parents[2] / "chipbench"


def test_new_config_workload_mix_and_metric_need_no_edit(tmp_path):
    base = tmp_path / "chipbench"
    shutil.copytree(BENCH, base, ignore=shutil.ignore_patterns(
        "__pycache__", "testdata"))
    spec = json.loads((base / "configs" / "qwen2-1.5b-grmac.json")
                      .read_text())
    spec["name"] = "new-model"
    spec["arch"]["n_layers"] = 4
    (base / "configs" / "new-model.json").write_text(json.dumps(spec))
    mix = {"loop": "open", "rate_per_s": 0.5, "block": 4, "requests": 8,
           "prompt": {"median": 64, "sigma": 0.5, "lo": 8, "hi": 256},
           "output": {"median": 16, "sigma": 0.5, "lo": 4, "hi": 64}}
    (base / "traffic" / "newmix.json").write_text(json.dumps(mix))
    (base / "workloads" / "new-model.newmix.json").write_text(json.dumps(
        {"runner": "serve", "serve": {"batch_slots": 2, "max_ctx": 512,
                                      "prefill_token_budget": 64}}))
    (base / "metrics" / "new_metric.burst.py").write_text(
        "def read(run):\n    return run['answer']\n")
    (base / "metrics" / "silent_metric.py").write_text(
        "def read(run):\n    return None\n")
    bench = {"workloads": [{"name": "new-model.newmix",
                            "config": "new-model", "traffic": "newmix",
                            "chips": 1}],
             "end_to_end": [{"name": "ttft_p90_ms", "unit": "ms",
                             "workloads": ["new-model.newmix"]},
                            {"name": "setup_s", "unit": "s"}],
             "per_layer": [{"name": "new_metric.burst", "unit": "%",
                            "moves": "ttft_p90_ms",
                            "workloads": ["new-model.newmix"]},
                           {"name": "silent_metric", "unit": "%",
                            "moves": "ttft_p90_ms"}]}
    cell = harness.load_cell("new-model.newmix", bench, base=base)
    assert cell["spec"]["arch"]["n_layers"] == 4
    assert cell["mix"] == mix
    assert cell["settings"]["serve"]["batch_slots"] == 2
    got = harness.read_layer_metrics(bench, "new-model.newmix",
                                     {"answer": 42.5}, base=base)
    # the silent reader found nothing and is left out of the line
    assert got == {"new_metric.burst": {"value": 42.5, "unit": "%"}}


def test_every_committed_cell_resolves():
    bench = harness.benchmark()
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"], bench)
        assert cell["spec"]["name"] == w["config"]
        assert cell["settings"]["runner"] == "serve"
    for m in bench["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))


def test_percentile_is_nearest_rank():
    xs = list(range(1, 11))
    assert harness.percentile(xs, 0.9) == 9
    assert harness.percentile(xs, 0.95) == 10
    assert harness.percentile(xs + [float("inf")], 0.95) == float("inf")
