"""Runs whose served path is broken underneath, with the look for a chip
skipped, come out not correct: once for each fault a serving cell can
have (a token altered where it is produced; a step that leaves its state
unchanged; a lane fed a token stream that is not its own)."""
from __future__ import annotations

import functools

import jax
import numpy as np
import pytest

from chipbench import run as bench_run
from chipbench import serve
from tinycell import TINY_GAP_LIMIT, tiny_args, tiny_cell, tiny_ssm_cell


def _run(make=tiny_cell):
    bench, cell = make()
    result, ctx = bench_run.run(tiny_args(), require_tpu=False,
                                bench=bench, cell=cell, compile_cache=False)
    return result, {c.name: c for c in ctx.checks}


def _broken_decode(fault, arch, sample):
    from repro.serving import engine
    raw = engine._decode_raw(arch, sample)

    def fn(params, toks, cache, *rest):
        ids, new_cache = raw(params, toks, cache, *rest)
        if fault == "token_altered":
            ids = ids.at[0].set((ids[0] + 1) % arch.vocab_size)
        elif fault == "state_unchanged":
            new_cache = cache
        return ids, new_cache
    return jax.jit(fn)


def _broken_prefill(arch, bucket):
    from repro.models import prefill_step

    def fn(p, t, c, i, l):
        logits, ids, _ = prefill_step(p, t, arch, c, i, l)
        return logits, ids, c          # the prompt never reaches the state
    return jax.jit(fn)


def _break(fault, monkeypatch):
    from repro.serving import engine
    if fault == "prefill_state_unchanged":
        monkeypatch.setattr(engine, "_prefill_fn",
                            functools.lru_cache()(_broken_prefill))
    else:
        monkeypatch.setattr(engine, "_decode_fn", functools.lru_cache()(
            functools.partial(_broken_decode, fault)))


@pytest.mark.parametrize("fault", [
    "token_altered", "state_unchanged", "prefill_state_unchanged"])
def test_broken_path_is_not_correct(fault, monkeypatch):
    _break(fault, monkeypatch)
    result, checks = _run()
    assert not result["correct"]
    assert (checks["max_logit_gap"].value > TINY_GAP_LIMIT
            or checks["state_error_worst_layer"].value > TINY_GAP_LIMIT)


@pytest.mark.parametrize("fault", [
    "token_altered", "state_unchanged", "prefill_state_unchanged"])
def test_ssm_broken_path_is_not_correct(fault, monkeypatch):
    """The same faults under the mamba2 cell, whose state each token
    overwrites: a decode step or a prefill chunk that keeps the state it
    was given, and a token altered where it is produced."""
    _break(fault, monkeypatch)
    result, checks = _run(tiny_ssm_cell)
    assert not result["correct"]
    assert (checks["max_logit_gap"].value > TINY_GAP_LIMIT
            or checks["state_error_worst_layer"].value > TINY_GAP_LIMIT)


def _feed_wrong(fault, engine):
    """Alter what the engine hands its programs, above the dispatch log:
    the programs and the log see the same wrong inputs, so only the check
    of each lane's token stream can tell."""
    prefill, decode = engine._compiled_prefill, engine._compiled_decode
    seen = {"chunks": 0}

    def compiled_prefill(bucket):
        fn = prefill(bucket)

        def call(params, toks, cache, index, lens):
            seen["chunks"] += 1
            if fault == "chunk_skipped" and seen["chunks"] == 2:
                index = index + lens      # a chunk's worth of prompt skipped
            return fn(params, toks, cache, index, lens)
        return call

    def compiled_decode(sample):
        fn = decode(sample)

        def call(params, toks, cache, lengths, active, *rest):
            if fault == "decode_token_stale":
                toks = toks.at[:, 0].set(jax.numpy.where(
                    np.asarray(active), (toks[:, 0] + 1) % 500, toks[:, 0]))
            return fn(params, toks, cache, lengths, active, *rest)
        return call

    engine._compiled_prefill = compiled_prefill
    engine._compiled_decode = compiled_decode


@pytest.mark.parametrize("fault", ["decode_token_stale", "chunk_skipped"])
def test_wrong_token_stream_is_not_correct(fault, monkeypatch):
    init = serve.DispatchLog.__init__

    def logged(self, engine, *args):
        init(self, engine, *args)
        _feed_wrong(fault, engine)
    monkeypatch.setattr(serve.DispatchLog, "__init__", logged)
    result, checks = _run()
    assert not result["correct"]
    assert checks["stream_faults"].value > 0
