"""Distributed train-step construction and the resilient training loop.

``make_train_step`` builds a pjit-able (params, opt_state, batch) ->
(params, opt_state, metrics) function with:

* microbatch gradient accumulation (sequential ``lax.scan`` over microbatch
  splits — activation memory / global-batch decoupling),
* optional int8 error-feedback gradient compression before the DP
  all-reduce boundary (OptimizerConfig.grad_compression),
* f32 gradient accumulation regardless of param dtype.

``train`` is the driver: restore-or-init, heartbeats, periodic atomic
checkpoints, straggler logging — everything the multi-pod launcher uses.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import init_params, train_loss
from repro.parallel.sharding import (
    batch_axes, get_mesh, named_sharding_tree, param_specs)
from repro.training import checkpoint as ckpt
from repro.training.fault import Heartbeat, HeartbeatBoard
from repro.training.optimizer import (
    OptimizerConfig,
    apply_updates,
    compress_grads,
    decompress_grads,
    init_opt_state,
)

__all__ = ["TrainConfig", "make_train_step", "init_train_state", "train"]


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    steps: int = 100
    microbatches: int = 1
    # durable checkpoint tier (object store / NFS in production). Written
    # asynchronously every ckpt_every steps by a background writer — the
    # training thread only pays the device_get snapshot.
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    # optional fast local tier (node-local SSD: lost with the node but
    # cheap to write often). None disables the tier.
    ckpt_local_dir: Optional[str] = None
    ckpt_local_every: int = 10
    log_every: int = 10
    # GR-MAC backend override for CIM-enabled archs (None keeps the arch's
    # CIMConfig.backend; see kernels.dispatch for the choices). Training
    # batches are large-M matmuls, so "auto" plans onto the fused tiled
    # backend; cim_tile_m / cim_tile_n pin its tile sizes when set.
    cim_backend: Optional[str] = None
    cim_tile_m: Optional[int] = None
    cim_tile_n: Optional[int] = None
    opt: OptimizerConfig = dataclasses.field(default_factory=OptimizerConfig)


def make_train_step(arch: ArchConfig, tcfg: TrainConfig) -> Callable:
    if tcfg.cim_backend is not None:
        arch = arch.replace(cim=arch.cim.with_backend(tcfg.cim_backend))
    if tcfg.cim_tile_m is not None or tcfg.cim_tile_n is not None:
        arch = arch.replace(cim=arch.cim.with_tiles(
            tcfg.cim_tile_m, tcfg.cim_tile_n))
    ocfg = tcfg.opt
    nmb = tcfg.microbatches

    def loss_fn(params, mb):
        total, metrics = train_loss(params, mb, arch)
        return total, metrics

    def train_step(params, opt_state, batch):
        if nmb == 1:
            (_, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        else:
            def split(x):
                b = x.shape[0]
                return x.reshape(nmb, b // nmb, *x.shape[1:])

            mbs = jax.tree.map(split, batch)
            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            zero_m = {"loss": jnp.zeros(()), "aux_loss": jnp.zeros(()),
                      "total": jnp.zeros(())}

            def acc_fn(carry, mb):
                g_acc, m_acc = carry
                (_, metrics), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, mb)
                g_acc = jax.tree.map(
                    lambda a, b_: a + b_.astype(jnp.float32) / nmb, g_acc, g)
                # accumulate the WHOLE metrics dict: MoE aux losses must
                # survive microbatching, not read as 0 in the logs
                m_acc = jax.tree.map(lambda a, m: a + m / nmb, m_acc, metrics)
                return (g_acc, m_acc), None

            (grads, metrics), _ = jax.lax.scan(acc_fn, (zero, zero_m), mbs)

        if ocfg.grad_compression:
            q, scales, err = compress_grads(grads, opt_state["err"])
            grads = decompress_grads(q, scales)   # DP all-reduce moves int8
            opt_state = dict(opt_state, err=err)

        params, opt_state, om = apply_updates(params, grads, opt_state, ocfg)
        metrics = dict(metrics, **om)
        return params, opt_state, metrics

    return train_step


def init_train_state(arch: ArchConfig, tcfg: TrainConfig, seed: int = 0):
    """Initial params, optimizer state and the jitted train step, placed
    for the active mesh (``parallel.sharding.use_mesh``).

    Returns ``(params, opt_state, step_fn, shardings)``. Without a mesh
    everything lives on the default device and ``shardings`` is None.
    Under a mesh, params and optimizer state are created directly in their
    ``param_specs`` shardings (FSDP over the data axes, TP over "model"),
    the step is jitted with matching in/out shardings so the state stays
    put from step to step (batches are split over the data axes), and
    ``shardings`` is the ``(params, opt_state)`` NamedSharding pair."""
    key = jax.random.PRNGKey(seed)
    init_p = lambda k: init_params(k, arch)
    init_o = lambda p: init_opt_state(p, tcfg.opt)
    mesh = get_mesh()
    if mesh is None:
        params = init_p(key)
        return (params, init_o(params), jax.jit(make_train_step(arch, tcfg)),
                None)
    p_shape = jax.eval_shape(init_p, key)
    p_sh = named_sharding_tree(param_specs(p_shape, mesh), mesh)
    o_sh = named_sharding_tree(
        param_specs(jax.eval_shape(init_o, p_shape), mesh), mesh)
    b_sh = NamedSharding(mesh, P(batch_axes(mesh)))
    params = jax.jit(init_p, out_shardings=p_sh)(key)
    opt_state = jax.jit(init_o, out_shardings=o_sh)(params)
    step_fn = jax.jit(make_train_step(arch, tcfg),
                      in_shardings=(p_sh, o_sh, b_sh),
                      out_shardings=(p_sh, o_sh, NamedSharding(mesh, P())))
    return params, opt_state, step_fn, (p_sh, o_sh)


def train(
    arch: ArchConfig,
    tcfg: TrainConfig,
    pipeline,
    *,
    seed: int = 0,
    heartbeat_dir: Optional[str] = None,
) -> dict:
    """Run (or resume) training; returns final metrics.

    State placement follows ``init_train_state``: sharded over the active
    mesh when there is one.

    Checkpointing is asynchronous and (optionally) two-tier: a background
    writer thread publishes atomic snapshots while training proceeds —
    the loop only pays the host snapshot (see
    ``checkpoint.AsyncCheckpointer``). Resume picks the freshest valid
    step across tiers, falling back past corrupt ones."""
    params, opt_state, step_fn, shardings = init_train_state(arch, tcfg, seed)
    start_step = 0

    writer = None
    if tcfg.ckpt_dir:
        writer = ckpt.AsyncCheckpointer(
            tcfg.ckpt_dir, tcfg.ckpt_local_dir,
            durable_every=tcfg.ckpt_every,
            local_every=tcfg.ckpt_local_every)
        try:
            state, start_step, tier = writer.restore(
                {"params": params, "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
            if shardings is not None:
                params, opt_state = jax.device_put((params, opt_state),
                                                   shardings)
            print(f"[train] resumed from step {start_step} ({tier} tier)")
        except FileNotFoundError:
            pass  # cold start

    board = HeartbeatBoard(heartbeat_dir) if heartbeat_dir else None

    metrics = {}
    try:
        for step in range(start_step, tcfg.steps):
            t0 = time.time()
            batch = pipeline.batch_at(step)
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            jax.block_until_ready(metrics["loss"])
            dt = time.time() - t0
            if board:
                board.beat(
                    Heartbeat(jax.process_index(), step, time.time(), dt))
            if step % tcfg.log_every == 0:
                print(f"[train] step {step} loss "
                      f"{float(metrics['loss']):.4f} ({dt*1e3:.0f} ms)")
            if writer:
                writer.maybe_save(step + 1,
                                  {"params": params, "opt": opt_state})
        if writer:
            writer.save(tcfg.steps, {"params": params, "opt": opt_state})
    finally:
        if writer:
            writer.close()
    return {k: float(v) for k, v in metrics.items()}
