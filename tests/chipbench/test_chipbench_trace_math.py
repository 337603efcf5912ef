"""The trace reduction's arithmetic on a hand-made trace whose numbers
are known: busy time, idle gaps by host span, program runs matched with
the logged dispatches, and the readers built on them."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import flops, harness, trace

SPEC = json.loads((Path(__file__).resolve().parents[2] / "chipbench"
                   / "configs" / "qwen2-1.5b-grmac.json").read_text())
PEAKS = flops.peaks("TPU v5 lite")
PROG = {"prefill": ["jit__lambda"], "decode": ["jit_fn"]}


def _kernel(m_pad, k, n_pad):
    return (f"%grmac_matmul_pallas.1 = f32[{m_pad},{n_pad}] custom-call("
            f"f32[{m_pad},{k}] %pad.1, f32[{k},{n_pad}] %w.1), "
            'custom_call_target="tpu_custom_call"')


def _op(name, start, dur):
    return trace.Op(name, start, dur)


def _run():
    ops = [
        # prefill program 0..1000: one fusion, one kernel (8 x 128 rows)
        _op("%fusion.1 = bf16[1024,1536] fusion()", 0, 400),
        _op(_kernel(1024, 1536, 8960), 400, 600),
        # decode program 1500..2000 (idle 1000..1500 under a host span);
        # the while op spans its body and is not counted twice
        _op("%while.2 = (s32[]) while(%t)", 1500, 500),
        _op("%fusion.2 = bf16[8,1536] fusion()", 1500, 100),
        _op(_kernel(128, 1536, 8960), 1600, 300),
        _op("%fusion.3 = bf16[8,1536] fusion()", 1900, 100),
        # a second decode 2500..2800, overlapping ops count once; the
        # head's N is padded from the vocabulary 151936 to 152064
        _op(_kernel(128, 1536, 152064), 2500, 300),
        _op("%fusion.4 = bf16[8,1536] fusion()", 2600, 100),
    ]
    # the harness's own lane copy runs between them under its own name
    mods = [("jit__lambda(3)", 0, 1000), ("jit_keep_lane_state(9)", 1100, 0),
            ("jit_fn(7)", 1500, 500), ("jit_fn(7)", 2500, 300)]
    host = [("chipbench.scheduler_step", 900, 700)]
    events = [
        ("prefill", np.zeros((8, 128)), np.array([0] * 8),
         np.array([100, 0, 0, 0, 0, 0, 0, 0])),
        ("decode", np.zeros((8, 1)), np.array([100, 5] + [0] * 6),
         np.array([True, True] + [False] * 6)),
        ("decode", np.zeros((8, 1)), np.array([101, 6] + [0] * 6),
         np.array([True, False] + [False] * 6)),
    ]
    return trace.RunData([trace.Device(mods, ops, host)], window_s=4e-6,
                         programs=PROG, events=events, spec=SPEC,
                         peaks=PEAKS, chips=1)


def test_busy_idle_and_gaps():
    run = _run()
    # busy: 0..1000, 1500..2000, 2500..2800
    assert run.busy_s == pytest.approx(1800e-9)
    gaps = dict(run.breakdown["idle_gaps"])
    assert gaps["host scheduler_step"] == pytest.approx(500e-9)
    assert gaps["between programs"] == pytest.approx(500e-9)
    ops = dict(run.breakdown["device_ops"])
    assert ops["grmac_kernel"] == pytest.approx(1200e-9)
    assert ops["fusion"] == pytest.approx(700e-9)
    assert "while" not in ops


def test_program_runs_match_the_log():
    run = _run()
    assert run.program_runs("prefill") == [1000]
    assert run.program_runs("decode") == [500, 300]
    assert run.prefill_tokens() == 100
    run.events.append(run.events[-1])       # one dispatch the trace lacks
    assert run.program_runs("decode") is None


def test_readers_on_the_known_trace():
    from chipbench.metrics import serving
    run = _run()
    assert serving.prefill_us_per_token(run) == pytest.approx(1000 / 1e3
                                                              / 100)
    assert serving.decode_step_ms(run) == pytest.approx(400 / 1e6)
    assert run.kernel_calls() == [((1024, 1536, 8960), 600),
                                  ((8, 1536, 8960), 300),
                                  ((8, 1536, 151936), 300)]
    floor = (flops.grmac_roofline_s(1024, 1536, 8960, SPEC, PEAKS)
             + flops.grmac_roofline_s(8, 1536, 8960, SPEC, PEAKS)
             + flops.grmac_roofline_s(8, 1536, 151936, SPEC, PEAKS))
    assert serving.grmac_roofline_pct(run) == pytest.approx(
        100 * floor / 1200e-9)
    dec = (flops.token_flops(SPEC, 100)
           + flops.token_flops(SPEC, 5)
           + flops.token_flops(SPEC, 101))
    assert run.decode_flops() == pytest.approx(dec)
    assert serving.program_mfu_pct(run, "decode") == pytest.approx(
        100 * dec / (800e-9 * PEAKS["bf16_flops_per_s"]))
    pre = flops.span_flops(SPEC, 0, 100)
    assert run.prefill_flops() == pytest.approx(pre)
    assert serving.program_mfu_pct(run, "prefill") == pytest.approx(
        100 * pre / (1000e-9 * PEAKS["bf16_flops_per_s"]))


def test_json_round_trip():
    run = _run()
    again = trace.from_json(json.loads(json.dumps(trace.to_json(
        run.devices))))
    assert again == run.devices


def test_a_reader_that_finds_nothing_is_left_out():
    run = trace.RunData([trace.Device([], [], [])], window_s=1.0,
                        programs=PROG, events=[], spec=SPEC, peaks=PEAKS,
                        chips=1)
    bench = {"end_to_end": [{"name": "itl_p95_ms", "unit": "ms"}],
             "per_layer": [{"name": n, "unit": "%", "moves": "itl_p95_ms"}
                           for n in ("grmac_roofline.chat",
                                     "prefill_us_per_token.chat",
                                     "decode_step_ms.chat",
                                     "decode_mfu_pct.chat")]}
    assert harness.read_layer_metrics(bench, "any", run) == {}
