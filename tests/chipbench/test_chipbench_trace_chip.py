"""The trace reduction on a small trace recorded on a TPU v5e chip: one
bucket-128 prefill and two decode steps of the chat cell (qwen2-1.5b,
GR-MAC, 8 slots), read by ``trace.load_xplane`` and cut to
``testdata/`` by ``trace.save_excerpt``."""
from __future__ import annotations

import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import flops, trace
from chipbench.metrics import serving

BENCH = Path(__file__).resolve().parents[2] / "chipbench"
SPEC = json.loads((BENCH / "configs" / "qwen2-1.5b-grmac.json").read_text())
PEAKS = flops.peaks("TPU v5 lite")
PROG = {"prefill": ["jit__lambda"], "decode": ["jit_fn"]}


@pytest.fixture(scope="module")
def run():
    data = json.loads(gzip.decompress(
        (BENCH / "testdata" / "chat_trace_tpu_v5e.json.gz").read_bytes()))
    devices = trace.from_json(data)
    lanes = np.array([True] + [False] * 7)
    events = [("prefill", np.zeros((8, 128)), np.zeros(8, int),
               np.array([128] + [0] * 7)),
              ("decode", np.zeros((8, 1)), np.array([128] + [0] * 7), lanes),
              ("decode", np.zeros((8, 1)), np.array([129] + [0] * 7), lanes)]
    return trace.RunData(devices, window_s=1.0, programs=PROG, events=events,
                         spec=SPEC, peaks=PEAKS, chips=1)


def test_program_runs_are_the_chips_module_events(run):
    assert run.program_runs("prefill") == [591464247]
    assert run.program_runs("decode") == [97599588, 97605280]
    assert serving.decode_step_ms(run) == pytest.approx(97.602434)
    assert serving.prefill_us_per_token(run) == pytest.approx(
        591464.247 / 128)


def test_every_projection_is_one_kernel_launch(run):
    calls = run.kernel_calls()
    # 28 layers x (q, k, v, o, up, gate, down) + the head, per program run
    assert len(calls) == 3 * (28 * 7 + 1)
    shapes = {s for s, _ in calls}
    assert (8, 1536, 8960) in shapes and (8, 8960, 1536) in shapes
    assert (8, 1536, 151936) in shapes and (1024, 1536, 256) in shapes
    share = serving.grmac_roofline_pct(run)
    assert 0.0 < share < 100.0


def test_busy_time_is_the_union_of_ops(run):
    d = run.devices[0]
    first, last = d.modules[0], d.modules[-1]
    span = last[1] + last[2] - first[1]
    assert 0.9 * (591464247 + 97599588 + 97605280) < run.busy_s * 1e9 \
        <= span
    labels = dict(run.breakdown["device_ops"])
    assert "grmac_kernel" in labels and "while" not in labels
