"""Training substrate: optimizer, pipeline determinism, checkpoint/restart,
fault detection, elastic planning, gradient compression, end-to-end loss
descent on a tiny model."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models import init_params
from repro.training import checkpoint as ckpt
from repro.training import fault
from repro.training.optimizer import (
    OptimizerConfig,
    apply_updates,
    compress_grads,
    decompress_grads,
    init_opt_state,
    schedule,
)
from repro.training.trainer import TrainConfig, make_train_step, train


# ---------------------------------------------------------------- optimizer
def test_schedule_shape():
    cfg = OptimizerConfig(lr=1e-3, warmup_steps=10, total_steps=100)
    s0 = float(schedule(cfg, jnp.asarray(0)))
    s10 = float(schedule(cfg, jnp.asarray(10)))
    s100 = float(schedule(cfg, jnp.asarray(100)))
    assert s0 < s10
    assert abs(s10 - 1e-3) < 1e-9
    assert s100 < s10


def test_adamw_descends_quadratic():
    params = {"w": jnp.asarray([3.0, -2.0])}
    cfg = OptimizerConfig(lr=0.1, warmup_steps=0, total_steps=200,
                          weight_decay=0.0)
    state = init_opt_state(params, cfg)
    for _ in range(150):
        grads = jax.tree.map(lambda p: 2 * p, params)
        params, state, m = apply_updates(params, grads, state, cfg)
    assert float(jnp.max(jnp.abs(params["w"]))) < 0.2


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_compression_error_feedback_bounded(seed):
    g = {"a": jax.random.normal(jax.random.PRNGKey(seed), (64,)) * 3.0}
    err = jax.tree.map(jnp.zeros_like, g)
    q, scales, err2 = compress_grads(g, err)
    rec = decompress_grads(q, scales)
    # per-tensor int8: error bounded by scale/2, and captured in residual
    scale = float(scales["a"])
    assert float(jnp.max(jnp.abs(rec["a"] + err2["a"] - g["a"]))) < 1e-5
    assert float(jnp.max(jnp.abs(rec["a"] - g["a"]))) <= scale / 2 + 1e-6


# ---------------------------------------------------------------- pipeline
def test_pipeline_deterministic_and_resumable():
    cfg = DataConfig(global_batch=4, seq_len=16, vocab_size=100, seed=7)
    p1, p2 = SyntheticLM(cfg), SyntheticLM(cfg)
    b5a, b5b = p1.batch_at(5), p2.batch_at(5)
    np.testing.assert_array_equal(b5a["inputs"], b5b["inputs"])
    assert not np.array_equal(p1.batch_at(6)["inputs"], b5a["inputs"])
    assert int(jnp.max(b5a["inputs"])) < 100
    np.testing.assert_array_equal(
        np.asarray(b5a["inputs"][:, 1:]), np.asarray(b5a["labels"][:, :-1]))


# ---------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip_and_atomicity(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
    d = str(tmp_path / "ckpt")
    ckpt.save_checkpoint(d, 3, tree)
    ckpt.save_checkpoint(d, 7, jax.tree.map(lambda x: x * 2, tree))
    assert ckpt.latest_step(d) == 7
    got, step = ckpt.restore_checkpoint(d, tree)
    assert step == 7
    np.testing.assert_array_equal(np.asarray(got["a"]),
                                  np.asarray(tree["a"]) * 2)
    # corrupt a file -> restore must fail loudly
    target = None
    for fn in os.listdir(os.path.join(d, "step_000000007")):
        if fn.endswith(".npy"):
            target = os.path.join(d, "step_000000007", fn)
            break
    with open(target, "r+b") as f:
        f.seek(100)
        f.write(b"\x00\x01\x02")
    with pytest.raises(IOError):
        ckpt.restore_checkpoint(d, tree, step=7)
    # previous step still loads
    got3, step3 = ckpt.restore_checkpoint(d, tree, step=3)
    assert step3 == 3


def test_checkpoint_retention(tmp_path):
    d = str(tmp_path / "ckpt")
    tree = {"x": jnp.zeros((2,))}
    for s in range(6):
        ckpt.save_checkpoint(d, s, tree, keep=2)
    steps = sorted(int(n.split("_")[1]) for n in os.listdir(d))
    assert steps == [4, 5]


# ---------------------------------------------------------------- fault
def test_failure_and_straggler_detection():
    now = 1000.0
    beats = [fault.Heartbeat(h, 10, now - (100.0 if h == 2 else 1.0), 1.0)
             for h in range(4)]
    beats[3].step_time_s = 10.0
    assert fault.detect_failures(beats, now, timeout_s=60) == [2]
    assert fault.detect_failures(beats, now, timeout_s=60,
                                 expected_hosts=6) == [2, 4, 5]
    assert fault.detect_stragglers(beats) == [3]


def test_heartbeat_board(tmp_path):
    board = fault.HeartbeatBoard(str(tmp_path / "hb"))
    board.beat(fault.Heartbeat(0, 5, time.time(), 0.5))
    board.beat(fault.Heartbeat(1, 5, time.time(), 0.6))
    got = board.read_all()
    assert sorted(b.host for b in got) == [0, 1]


def test_plan_remesh():
    shape, axes = fault.plan_remesh(512, 16)
    assert shape == (2, 16, 16) and axes == ("pod", "data", "model")
    # lose a pod's worth: fall back to single-pod style mesh
    shape, axes = fault.plan_remesh(256, 16)
    assert shape == (16, 16) and axes == ("data", "model")
    # odd survivor count: largest DP multiple
    shape, axes = fault.plan_remesh(250, 16)
    assert shape[-2] * shape[-1] <= 250 and shape[-1] == 16
    with pytest.raises(RuntimeError):
        fault.plan_remesh(8, 16)


# ---------------------------------------------------------------- end-to-end
def test_train_descends_and_restarts(tmp_path):
    arch = get_config("qwen2-1.5b").reduced().replace(n_layers=2)
    dcfg = DataConfig(global_batch=4, seq_len=32, vocab_size=arch.vocab_size,
                      seed=1)
    pipe = SyntheticLM(dcfg)
    ckdir = str(tmp_path / "ck")
    tcfg = TrainConfig(
        steps=6, ckpt_dir=ckdir, ckpt_every=3, log_every=100,
        opt=OptimizerConfig(lr=1e-3, warmup_steps=2, total_steps=6))
    train(arch, tcfg, pipe, seed=0)
    assert ckpt.latest_step(ckdir) == 6
    # "crash" after step 6, extend run, resume from checkpoint
    tcfg2 = TrainConfig(
        steps=8, ckpt_dir=ckdir, ckpt_every=3, log_every=100,
        opt=OptimizerConfig(lr=1e-3, warmup_steps=2, total_steps=8))
    m2 = train(arch, tcfg2, pipe, seed=0)
    assert np.isfinite(m2["loss"])


def test_train_step_microbatched_matches_full():
    arch = get_config("stablelm-3b").reduced().replace(n_layers=2)
    params = init_params(jax.random.PRNGKey(0), arch)
    dcfg = DataConfig(global_batch=4, seq_len=32, vocab_size=arch.vocab_size)
    batch = SyntheticLM(dcfg).batch_at(0)
    o1 = init_opt_state(params, OptimizerConfig())
    t1 = TrainConfig(microbatches=1)
    t2 = TrainConfig(microbatches=2)
    p1, _, m1 = make_train_step(arch, t1)(params, o1, batch)
    o2 = init_opt_state(params, OptimizerConfig())
    p2, _, m2 = make_train_step(arch, t2)(params, o2, batch)
    # same data -> same gradients (up to accumulation order): params close
    d = max(float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    assert d < 5e-2, d


def test_grad_compression_trains():
    arch = get_config("stablelm-3b").reduced().replace(n_layers=2)
    params = init_params(jax.random.PRNGKey(0), arch)
    ocfg = OptimizerConfig(grad_compression=True, lr=1e-3)
    ostate = init_opt_state(params, ocfg)
    tcfg = TrainConfig(opt=ocfg)
    step = make_train_step(arch, tcfg)
    dcfg = DataConfig(global_batch=4, seq_len=32, vocab_size=arch.vocab_size)
    pipe = SyntheticLM(dcfg)
    losses = []
    for s in range(5):
        params, ostate, m = step(params, ostate, pipe.batch_at(s))
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 1.2  # descends (noisy small-scale)


def test_adafactor_descends_and_state_is_small():
    from repro.training.optimizer import apply_updates as au
    params = {"w": jnp.ones((64, 32)), "b": jnp.zeros((32,))}
    cfg = OptimizerConfig(lr=0.1, warmup_steps=0, total_steps=100,
                          weight_decay=0.0, algorithm="adafactor")
    st = init_opt_state(params, cfg)
    # factored second moment: O(rows+cols), not O(rows*cols)
    assert st["vr"]["w"].shape == (64,)
    assert st["vc"]["w"].shape == (32,)
    p = params
    for _ in range(80):
        g = jax.tree.map(lambda x: 2 * x, p)
        p, st, _ = au(p, g, st, cfg)
    assert float(jnp.max(jnp.abs(p["w"]))) < 0.3


def test_adafactor_trains_tiny_model():
    arch = get_config("stablelm-3b").reduced().replace(n_layers=2)
    ocfg = OptimizerConfig(lr=1e-3, algorithm="adafactor")
    params = init_params(jax.random.PRNGKey(0), arch)
    state = init_opt_state(params, ocfg)
    step = make_train_step(arch, TrainConfig(opt=ocfg))
    pipe = SyntheticLM(DataConfig(global_batch=4, seq_len=32,
                                  vocab_size=arch.vocab_size))
    losses = []
    for s in range(6):
        params, state, m = step(params, state, pipe.batch_at(s))
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_elastic_end_to_end(tmp_path):
    """Full failure-recovery cycle: train -> checkpoint -> lose chips ->
    plan a smaller mesh -> reshard -> resume training losslessly."""
    arch = get_config("stablelm-3b").reduced().replace(n_layers=2)
    dcfg = DataConfig(global_batch=4, seq_len=32, vocab_size=arch.vocab_size)
    pipe = SyntheticLM(dcfg)
    ckdir = str(tmp_path / "ck")
    ocfg = OptimizerConfig(lr=1e-3, warmup_steps=1, total_steps=8)
    tcfg = TrainConfig(steps=4, ckpt_dir=ckdir, ckpt_every=2, log_every=100,
                       opt=ocfg)
    train(arch, tcfg, pipe, seed=0)

    # simulate: only 1 "chip" survives; plan keeps model_parallel=1
    shape, axes = fault.plan_remesh(1, 1, pod_size=256)
    assert shape == (1, 1) and axes == ("data", "model")
    from repro.compat import make_mesh
    mesh = make_mesh(shape, axes)
    params = init_params(jax.random.PRNGKey(0), arch)
    opt = init_opt_state(params, ocfg)
    state, step = ckpt.restore_checkpoint(
        ckdir, {"params": params, "opt": opt})
    from repro.parallel.sharding import param_specs
    specs = {"params": param_specs(params, mesh),
             "opt": param_specs(opt, mesh)}
    resharded = fault.reshard_tree(state, mesh, specs)
    assert step == 4
    # resume two more steps on the new mesh
    stepper = make_train_step(arch, TrainConfig(opt=ocfg))
    p, o = resharded["params"], resharded["opt"]
    for s in range(step, step + 2):
        p, o, m = stepper(p, o, pipe.batch_at(s))
        assert np.isfinite(float(m["loss"]))


_FSDP_PLACEMENT_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
from repro.compat import make_mesh
from repro.configs import get_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.parallel.sharding import use_mesh
from repro.training.optimizer import OptimizerConfig
from repro.training.trainer import TrainConfig, init_train_state, train

arch = get_config("qwen2-1.5b").reduced().replace(n_layers=2)
pipe = SyntheticLM(DataConfig(global_batch=8, seq_len=16,
                              vocab_size=arch.vocab_size))
tcfg = TrainConfig(steps=2, log_every=100,
                   opt=OptimizerConfig(lr=1e-3, warmup_steps=1, total_steps=2))
with use_mesh(make_mesh((4, 1), ("data", "model"))):
    params, opt_state, _, _ = init_train_state(arch, tcfg)
    n_sharded = 0
    for leaf in jax.tree.leaves((params, opt_state)):
        if "data" in str(leaf.sharding.spec):
            n_sharded += 1
            assert {s.data.size * 4 for s in leaf.addressable_shards} == {
                leaf.size}, (leaf.shape, leaf.sharding.spec)
    assert n_sharded > 0
    sharded = train(arch, tcfg, pipe)
single = train(arch, tcfg, pipe)
assert abs(sharded["loss"] - single["loss"]) < 1e-4, (sharded, single)
print("OK", n_sharded)
"""


def test_train_places_state_on_the_mesh():
    """Under a (data, model) = (4, 1) mesh, ``train`` creates params and
    optimizer state in their FSDP shardings (each device holds a quarter of
    every data-sharded leaf) and reaches the unsharded run's loss."""
    import subprocess
    import sys
    out = subprocess.run([sys.executable, "-c", _FSDP_PLACEMENT_SNIPPET],
                         capture_output=True, text=True, timeout=600,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
