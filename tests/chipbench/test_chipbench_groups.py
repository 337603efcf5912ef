"""What the harness knows of an architecture comes from its reference
module, and the store and the replay cover every layer group of the
program's cache: the dense cell reads as it did before its layout moved
into its module, and a pattern of two kinds with a tail keeps every
group."""
from __future__ import annotations

import hashlib

import jax
import numpy as np

from chipbench import harness, reference, serve, weights
from chipbench.reference import load_model, replay
from chipbench.reference.grmac import CONTROL, REFERENCE
from chipbench.traffic.generator import make_requests
from tinycell import tiny_cell


def _drive(arch, params, serve_cfg, reqs, kinds, watch=None):
    """Every request submitted at once and served to the end, logged;
    ``watch(engine)`` may wrap the engine's seams over the log's."""
    from repro.serving.engine import Engine
    from repro.serving.params import SamplingParams
    from repro.serving.scheduler import Scheduler
    scfg, qcfg = serve.engine_configs(serve_cfg)
    eng = Engine(arch, params, scfg)
    log = serve.DispatchLog(eng, len(reqs),
                            serve.chunk_bound(reqs, serve_cfg), kinds)
    if watch is not None:
        watch(eng)
    sched = Scheduler(eng, qcfg)
    rs = [sched.submit(r.prompt, params=SamplingParams(
        max_tokens=r.max_tokens)) for r in reqs]
    while not sched.idle():
        sched.step()
    return eng, log, rs


def test_dense_cell_reads_as_before_the_module_split():
    """The same weights from the same seed, bit for bit, and the same
    replay of a fixed log, reference and float8 control, as the harness
    read before the dense layout moved into ``reference/dense_gqa.py``."""
    bench, cell = tiny_cell()
    spec, serve_cfg = cell["spec"], cell["settings"]["serve"]
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            weights.make_params(spec, 2 ** 33 + 5))[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == ("bbace5eb34ebcc6e07f42a6fd1ad556572f82021381194"
                             "107819e2a957866c04")
    params = weights.make_params(spec, 7)
    reqs = make_requests(cell["mix"], 7, spec["arch"]["vocab_size"])[:8]
    eng, log, rs = _drive(harness.arch_from_spec(spec), params, serve_cfg,
                          reqs, reference.state_kinds(spec))
    b = serve_cfg["batch_slots"]
    host = replay.host_events(log.logged())
    w = replay.walk(host, b)
    assert not replay.stream_faults(w, [(r.prompt, r.generated) for r in rs])
    replay.attach_final_state(w, serve.state_groups(eng.cache))
    res = replay.run(load_model(spec, params), w, host, REFERENCE["float32"],
                     [replay.as_choices(w.served, b)], b,
                     serve_cfg["max_ctx"], others=[CONTROL["float32"]])
    assert len(host) == 48
    assert replay.gaps(res, w.served).size == 53
    assert replay.gaps(res, w.served).max() == 0.0
    assert res.state_error == [0.0, 0.0]
    assert res.other_state_error[0] == [0.038445296465331756,
                                        0.23126779550604598]
    assert replay.gaps(res, w.served, 1).max() == np.float32(
        0.20894116163253784)


def test_two_kinds_and_a_tail_all_go_into_the_store():
    """A pattern of an SSD and an attention layer over three layers: two
    stacked groups and a tail. Each lane handed on keeps every group's
    state in the store as the program had it, each prefill chunk keeps the
    recurrent groups' state of the lane it fed, and the cache's groups are
    the ones ``reference.cache_groups`` names."""
    from repro.configs.base import ArchConfig
    from repro.models import init_params
    from repro.serving.engine import Engine
    arch = ArchConfig(name="tiny-hybrid", family="hybrid", n_layers=3,
                      d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
                      d_ff=128, vocab_size=300, block_pattern=("ssm", "attn"),
                      ssm_state=16, ssm_headdim=16, dtype="float32")
    a = {"n_layers": 3, "block_pattern": ["ssm", "attn"]}
    groups = reference.cache_groups(a)
    assert groups == [("b0_ssm", "ssm", 1), ("b1_attn", "attn", 1),
                      ("t0_ssm", "ssm", 1)]
    kinds = {"b0_ssm": "recurrent", "b1_attn": "positional",
             "t0_ssm": "recurrent"}
    serve_cfg = {"batch_slots": 2, "max_ctx": 64, "prefill_token_budget": 8}
    mix = {"loop": "open", "rate_per_s": 1.0, "block": 4, "requests": 4,
           "prompt": {"median": 12, "sigma": 0.3, "lo": 6, "hi": 20},
           "output": {"median": 4, "sigma": 0.3, "lo": 2, "hi": 6}}
    reqs = make_requests(mix, 3, arch.vocab_size)
    params = init_params(jax.random.PRNGKey(0), arch)
    # each lane's state just before the engine resets it, and the fed
    # lane's state after each prefill chunk, as the program has them
    handed, fed = [], []

    def watch(eng):
        reset, prefill = eng._reset_slot_state, eng._compiled_prefill

        def reset_slot_state(slot):
            handed.append(_lane(eng.cache, slot))
            reset(slot)

        def compiled_prefill(bucket):
            fn = prefill(bucket)

            def call(params, toks, cache, index, lens):
                out = fn(params, toks, cache, index, lens)
                fed.append(_lane(out[2], int(np.argmax(np.asarray(lens)))))
                return out
            return call
        eng._reset_slot_state = reset_slot_state
        eng._compiled_prefill = compiled_prefill

    eng, log, _ = _drive(arch, params, serve_cfg, reqs, kinds, watch)
    assert sorted(serve.state_groups(eng.cache)) == sorted(kinds)
    assert serve.state_groups(eng.cache)["t0_ssm"]["h"].shape[:2] == (1, 2)
    events = log.logged()
    ends = [e[2] for e in events if e[0] == "end"]
    chunks = [e[1] for e in events if e[0] == "chunk"]
    assert len(ends) == len(handed) >= 2
    assert len(chunks) == len(fed) == sum(
        e[0] == "prefill" for e in events) >= 4
    for snap, want in zip(ends, handed):
        assert sorted(snap.tree) == sorted(kinds)
        _assert_equal(_stored(snap), want)
    for snap, want in zip(chunks, fed):
        assert sorted(snap.tree) == ["b0_ssm", "t0_ssm"]
        _assert_equal(_stored(snap), {g: want[g] for g in snap.tree})


def _lane(cache, slot):
    return jax.tree.map(lambda a: np.asarray(a[:, slot]),
                        serve.state_groups(cache))


def _stored(snap):
    return jax.tree.map(lambda a: np.asarray(a[snap.index]), snap.tree)


def _assert_equal(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    jax.tree.map(np.testing.assert_array_equal, got, want)
