"""A whole run of a serving cell at toy widths on the CPU, the look for a
chip skipped: sound runs come out correct, and the float8 control does
not."""
from __future__ import annotations

import json
import os
import subprocess
import sys

from chipbench import run as bench_run
from tinycell import ROOT, TINY_GAP_LIMIT, tiny_args, tiny_cell, tiny_ssm_cell


def test_sound_run_is_correct():
    bench, cell = tiny_cell()
    result, ctx = bench_run.run(tiny_args(), require_tpu=False, bench=bench,
                                cell=cell, compile_cache=False)
    checks = {c.name: c for c in ctx.checks}
    assert result["correct"], checks
    assert checks["max_logit_gap"].value <= TINY_GAP_LIMIT
    assert checks["stream_faults"].value == 0
    assert checks["served_tokens"].value >= 10
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) >= {"setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())


def test_float8_control_fails_the_limit():
    """The control, the reference one precision step below the configured
    one, and the altered-token fault read gaps above the limits where the
    program reads none."""
    from chipbench import control
    bench, cell = tiny_cell()
    ctx = bench_run.Context(tiny_args(), cell, bench)
    r = control.control_readings(ctx)
    assert r["stream_faults"] == 0
    assert r["program"]["max_gap"] <= TINY_GAP_LIMIT
    assert max(r["program"]["state_error"]) <= TINY_GAP_LIMIT
    assert max(r["control"]["state_error"]) > TINY_GAP_LIMIT
    assert r["altered"]["max_gap"] > TINY_GAP_LIMIT
    assert r["tokens"] >= 10
    # held to the cell's limits, the program passes and the others fail
    assert r["program"]["correct"]
    assert not r["control"]["correct"] and not r["altered"]["correct"]


def _bfloat16_readings(*cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "chipbench" / "tinycell.py"),
         "bfloat16", *cell], env=env, capture_output=True, text=True,
        timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_bfloat16_program_equals_the_reference_bit_for_bit():
    """In bfloat16, with XLA held to the stated precision as the harness
    holds it, the served program and the reference agree exactly on every
    layer and token; the float8 control does not. (A process of its own:
    the flag has to be set before JAX starts.)"""
    _assert_exact(_bfloat16_readings())


def _assert_exact(r):
    assert r["stream_faults"] == 0 and r["tokens"] >= 10
    assert r["program"]["max_gap"] == 0.0
    assert max(r["program"]["state_error"]) == 0.0
    assert max(r["control"]["state_error"]) > 0.05
    assert r["control"]["max_gap"] > 0.0


def test_ssm_sound_run_is_correct():
    """A whole run of the tiny mamba2 cell: every prefill chunk and decode
    step replayed from the lanes' SSD states as the program wrote them."""
    bench, cell = tiny_ssm_cell()
    result, ctx = bench_run.run(tiny_args(), require_tpu=False, bench=bench,
                                cell=cell, compile_cache=False)
    checks = {c.name: c for c in ctx.checks}
    assert result["correct"], checks
    assert checks["max_logit_gap"].value <= TINY_GAP_LIMIT
    assert checks["state_error_worst_layer"].value <= TINY_GAP_LIMIT
    assert checks["stream_faults"].value == 0
    assert checks["served_tokens"].value >= 10
    kept = [e for e in ctx.log_events if e[0] == "chunk"]
    assert len(kept) == sum(e[0] == "prefill" for e in ctx.log_events) > 0


def test_ssm_float8_control_and_altered_token_fail():
    from chipbench import control
    bench, cell = tiny_ssm_cell()
    r = control.control_readings(bench_run.Context(tiny_args(), cell, bench))
    assert r["stream_faults"] == 0 and r["tokens"] >= 10
    assert r["program"]["correct"]
    assert max(r["program"]["state_error"]) <= TINY_GAP_LIMIT
    assert max(r["control"]["state_error"]) > TINY_GAP_LIMIT
    assert r["altered"]["max_gap"] > TINY_GAP_LIMIT
    assert not r["control"]["correct"] and not r["altered"]["correct"]


def test_ssm_bfloat16_program_equals_the_reference_bit_for_bit():
    _assert_exact(_bfloat16_readings("ssm"))
