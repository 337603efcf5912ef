"""The serving path's host spans (``repro.serving.spans``) in the
profiler's own trace, the serving programs' names, and the engine's
``prefill_rows`` counter (the GR-MAC launch's per-site name, which needs
the TPU's compiler, is in ``test_tpu_compile.py``)."""
import glob

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import init_params
from repro.serving import engine as engine_mod
from repro.serving.engine import Engine, ServeConfig
from repro.serving.params import SamplingParams
from repro.serving.scheduler import Scheduler, SchedulerConfig

_ARCH = get_config("qwen2-1.5b").reduced()


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), _ARCH)


def _spans(trace_dir):
    """(name, start, end, args) of every ``repro.*`` host span."""
    from jax.profiler import ProfileData
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name[len("repro."):], e.start_ns, e.end_ns,
                         dict(e.stats)) for e in line.events
                        if e.name.startswith("repro.")]
    return sorted(out, key=lambda s: s[1])


def _inside(child, parents):
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


@pytest.fixture(scope="module")
def traced(params, tmp_path_factory):
    """Three requests through two slots (so one lane is reset), traced."""
    d = tmp_path_factory.mktemp("trace")
    eng = Engine(_ARCH, params, ServeConfig(batch_slots=2, max_ctx=64))
    sched = Scheduler(eng, SchedulerConfig(prefill_token_budget=16))
    reqs = [sched.submit(list(range(1, n + 1)),
                         params=SamplingParams(max_tokens=3))
            for n in (20, 5, 9)]
    with jax.profiler.trace(str(d)):
        while not sched.idle():
            sched.step()
    return _spans(d), reqs, eng, sched


def test_scheduler_phases_nest_in_its_steps(traced):
    spans, reqs, _, _ = traced
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    steps = by["scheduler.step"]
    assert len(steps) >= 4
    for name in ("scheduler.admit", "scheduler.prefill", "scheduler.decode"):
        assert by[name] and all(_inside(s, steps) for s in by[name]), name
    # one admit span per request, carrying its id
    assert sorted(s[3]["rid"] for s in by["scheduler.admit"]) == \
        sorted(r.rid for r in reqs)
    assert all(_inside(s, by["scheduler.prefill"])
               for s in by["engine.prefill_chunk"] + by["engine.first_token"])
    assert all(_inside(s, by["scheduler.decode"]) for s in by["engine.step"])
    # every fetch waits inside the engine phase that needed it
    assert all(_inside(s, by["engine.step"] + by["engine.first_token"])
               for s in by["engine.fetch"])
    assert any(_inside(s, by["engine.step"]) for s in by["engine.fetch"])
    # the third request reuses a lane: it is reset inside its admission
    resets = by["engine.reset_lane"]
    assert resets and all(_inside(s, by["scheduler.admit"]) for s in resets)


def test_prefill_chunk_spans_carry_the_request(traced):
    spans, reqs, eng, sched = traced
    chunks = [s[3] for s in spans if s[0] == "engine.prefill_chunk"]
    # a 16-token budget: 20 -> 16 + 4, 5, 9
    assert sum(c["tokens"] for c in chunks) == 20 + 5 + 9
    assert {c["rid"] for c in chunks} == {r.rid for r in reqs}
    assert all(set(c) == {"slot", "rid", "tokens", "bucket"} for c in chunks)
    assert sum(c["tokens"] for c in chunks) == eng.stats["prefill_tokens"]
    # each chunk runs both lanes of its bucket; the operator reads the
    # count in the scheduler's metrics
    assert sum(2 * c["bucket"] for c in chunks) == eng.stats["prefill_rows"]
    assert sched.metrics()["prefill_rows_dispatched"] == \
        eng.stats["prefill_rows"]


@pytest.mark.parametrize("budget,rows", [(None, 4 * 32), (16, 4 * (16 + 8))])
def test_prefill_rows_count_every_lane_of_each_chunk(params, budget, rows):
    eng = Engine(_ARCH, params, ServeConfig(batch_slots=4, max_ctx=64))
    sched = Scheduler(eng, SchedulerConfig(prefill_token_budget=budget))
    sched.submit(list(range(1, 21)), params=SamplingParams(max_tokens=2))
    while not sched.idle():
        sched.step()
    assert eng.stats["prefill_tokens"] == 20
    assert eng.stats["prefill_rows"] == rows


def _module_name(lowered) -> str:
    head = lowered.as_text().split("\n", 1)[0]
    return head.split("@", 1)[1].split()[0].strip('"')


def test_serving_programs_have_stable_names(params):
    eng = Engine(_ARCH, params, ServeConfig(batch_slots=2, max_ctx=64))
    b = 2
    z = jnp.zeros((b,), jnp.int32)
    tail = (jax.random.PRNGKey(0), jnp.zeros((b,), jnp.float32), z - 1, z)
    pre = engine_mod._prefill_fn(eng.arch, 16).lower(
        eng.params, jnp.zeros((b, 16), jnp.int32), eng.cache, z, z)
    names = [_module_name(pre)]
    for sample in (False, True):
        names.append(_module_name(engine_mod._decode_fn(eng.arch, sample).lower(
            eng.params, jnp.zeros((b, 1), jnp.int32), eng.cache, z,
            jnp.zeros((b,), bool), *tail)))
    ver = engine_mod._verify_fn(eng.arch, 8).lower(
        eng.params, jnp.zeros((b, 8), jnp.int32), eng.cache, z, z, *tail)
    names.append(_module_name(ver))
    assert names == ["jit_prefill_b16", "jit_decode_greedy",
                     "jit_decode_sample", "jit_verify_b8"]
