"""Chip smoke run: the GR-MAC serving path at qwen2-1.5b's published widths
on one TPU chip.

    python chip_smoke.py                 # kernel + serving phases, one chip
    python chip_smoke.py --four-chips    # sharded train phase only, 4 chips

Phases (one process, run in order; a failed phase is reported and the
script exits non-zero without printing a result):

kernel   The Pallas GR-MAC kernel at a real width (M = 4 decode rows and
         M = 256 prefill rows, K = 1536, N = 8960) for conv/row/unit: the
         dispatch planner must pick ``pallas``, the compiled program must
         hold the kernel (``tpu_custom_call``), and its output must equal
         the ``ref`` oracle run on the same chip.
serve    qwen2-1.5b (all 28 layers, random weights from ``--seed``) with
         GR-MAC on every projection, served through ``Engine`` and
         ``Scheduler``: four requests with prompts of 128/300/512/1000
         tokens decode 32 greedy tokens each, twice; every token must be in
         the vocabulary, every request must finish, and the second run must
         repeat the first token for token.
train    (``--four-chips`` only) ``train()`` steps of qwen2-1.5b at
         published widths cut to 4 layers on a (data, model) = (4, 1) mesh,
         against the same steps on one chip of that host: the losses must
         agree, and every FSDP-sharded leaf of the params and optimizer
         state must be split in quarters across the chips.

Times printed are host wall-clock seconds, not device metrics. The last
line of standard output is the JSON result, printed only when every phase
passed. The script needs a TPU and exits non-zero on any other platform.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "qwen2-1.5b"
PROMPT_LENS = (128, 300, 512, 1000)
NEW_TOKENS = 32
SLOTS = 4
MAX_CTX = 2048
KERNEL_SHAPES = ((4, 1536, 8960), (256, 1536, 8960))
GRANULARITIES = ("conv", "row", "unit")
TRAIN_LAYERS = 4
TRAIN_STEPS = 3
TRAIN_BATCH = 8
TRAIN_SEQ = 128
# |loss(4 chips) - loss(1 chip)| bound after TRAIN_STEPS steps. The FSDP
# program reduces the batch mean and the gradients in another order than
# the one-chip program, on bf16 weights and activations. bf16 carries 8
# significand bits (relative step 2^-8 ~ 0.4%), and the loss averages
# TRAIN_BATCH * TRAIN_SEQ per-token terms, so the reordering error sits well
# below one bf16 step of the 5-25 nat losses of these first steps
# (0.02-0.1).
LOSS_ATOL = 0.02


def log(msg: str) -> None:
    print(msg, flush=True)


def kernel_phase(seed: int) -> None:
    import dataclasses

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core.formats import quantize
    from repro.kernels import dispatch

    base = get_config(ARCH).cim.with_mode("grmac")
    failures = []
    for gran in GRANULARITIES:
        cim = dataclasses.replace(base, granularity=gran)
        kw = dict(fmt_x=cim.fmt_x, fmt_w=cim.fmt_w, n_r=cim.n_r,
                  enob=cim.resolved_enob(), granularity=gran)
        for m, k, n in KERNEL_SHAPES:
            plan = dispatch.plan_for(m, k, n, **kw)
            if plan.backend != "pallas":
                raise AssertionError(
                    f"{gran} {m}x{k}x{n}: planned {plan.backend!r}, "
                    "expected 'pallas'")
            kx, kw_ = jax.random.split(jax.random.PRNGKey(seed))
            x = jax.random.uniform(kx, (m, k), minval=-1.0, maxval=1.0)
            w = quantize(jax.random.uniform(kw_, (k, n), minval=-1.0,
                                            maxval=1.0), cim.fmt_w)
            fn = jax.jit(lambda a, b: dispatch.grmac_matmul(a, b, **kw))
            oracle = jax.jit(
                lambda a, b: dispatch.grmac_matmul(a, b, backend="ref", **kw))
            t0 = time.perf_counter()
            compiled = fn.lower(x, w).compile()
            t_compile = time.perf_counter() - t0
            if "tpu_custom_call" not in compiled.as_text():
                raise AssertionError(
                    f"{gran} {m}x{k}x{n}: no tpu_custom_call in the "
                    "compiled program")
            out = np.asarray(jax.block_until_ready(compiled(x, w)))
            ref = np.asarray(oracle(x, w))
            diff = np.abs(out - ref)
            n_bad = int(np.count_nonzero(diff))
            log(f"[kernel] {gran:4s} m={m:3d} k={k} n={n}: plan=pallas "
                f"tpu_custom_call=yes compile_wall_s={t_compile:.3f} "
                f"mismatches={n_bad}/{diff.size} "
                f"max_abs_diff={float(diff.max()):.6g} "
                f"max_abs_ref={float(np.abs(ref).max()):.6g}")
            if not np.isfinite(out).all():
                failures.append(f"{gran} {m}x{k}x{n}: non-finite output")
            if n_bad:
                failures.append(f"{gran} {m}x{k}x{n}: kernel differs from "
                                f"the ref oracle in {n_bad} of {diff.size} "
                                "outputs")
    if failures:
        raise AssertionError("; ".join(failures))


def _serve_once(arch, params, prompts):
    from repro.serving.engine import Engine, ServeConfig
    from repro.serving.params import SamplingParams
    from repro.serving.scheduler import Scheduler, SchedulerConfig

    eng = Engine(arch, params, ServeConfig(batch_slots=SLOTS, max_ctx=MAX_CTX))
    # no prefill budget: each admitted prompt prefills in full at admission
    sched = Scheduler(eng, SchedulerConfig(prefill_token_budget=None))
    reqs = [sched.submit(p, params=SamplingParams(max_tokens=NEW_TOKENS))
            for p in prompts]
    t0 = time.perf_counter()
    while not sched.idle():
        sched.step()
    wall = time.perf_counter() - t0
    # scheduler stamps come after each step's device-to-host fetch
    prefill_s = max(r.wall_first for r in reqs) - min(r.wall_admit
                                                      for r in reqs)
    decode_s = (max(r.wall_finish for r in reqs)
                - max(r.wall_first for r in reqs))
    return reqs, wall, prefill_s, decode_s


def serve_phase(seed: int) -> None:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import init_params

    arch = get_config(ARCH)
    arch = arch.replace(cim=arch.cim.with_mode("grmac"))
    log(f"[serve] {arch.name}: {arch.n_layers} layers, d_model "
        f"{arch.d_model}, d_ff {arch.d_ff}, vocab {arch.vocab_size}, "
        f"cim mode={arch.cim.mode} granularity={arch.cim.granularity} "
        f"n_r={arch.cim.n_r} backend={arch.cim.backend}")
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(lambda key: init_params(key, arch))(jax.random.PRNGKey(seed)))
    n_params = sum(int(a.size) for a in jax.tree.leaves(params))
    log(f"[serve] params {n_params} ({arch.dtype}) init_wall_s="
        f"{time.perf_counter() - t0:.3f}")
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, arch.vocab_size, n).tolist()
               for n in PROMPT_LENS]

    runs, walls = [], []
    for label in ("cold", "warm"):
        reqs, wall, prefill_s, decode_s = _serve_once(arch, params, prompts)
        walls.append(wall)
        log(f"[serve] {label} run: wall_s={wall:.3f} "
            f"prefill_wall_s={prefill_s:.3f} decode_wall_s={decode_s:.3f}"
            + (" (cold run includes compilation)" if label == "cold" else ""))
        for r in reqs:
            if r.finish_reason != "length" or r.n_generated != NEW_TOKENS:
                raise AssertionError(
                    f"request {r.rid} (prompt {len(r.prompt)}): finished "
                    f"{r.finish_reason!r} with {r.n_generated} tokens, "
                    f"expected 'length' with {NEW_TOKENS}")
            bad = [t for t in r.generated if not 0 <= t < arch.vocab_size]
            if bad:
                raise AssertionError(
                    f"request {r.rid}: out-of-vocabulary tokens {bad[:8]}")
        runs.append([list(r.generated) for r in reqs])
    log(f"[serve] compile_wall_s={walls[0] - walls[1]:.3f} (cold run minus "
        "warm run)")
    for n, toks in zip(PROMPT_LENS, runs[0]):
        log(f"[serve] prompt {n:4d} -> {len(toks)} tokens, first 8: "
            f"{toks[:8]}")
    if runs[0] != runs[1]:
        raise AssertionError("second greedy run differs from the first")
    log(f"[serve] {len(PROMPT_LENS)} requests x {NEW_TOKENS} tokens served; "
        "second greedy run identical to the first")


def train_phase(seed: int) -> None:
    import jax
    import numpy as np
    from jax.sharding import AxisType, Mesh

    from repro.configs import get_config
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.parallel.sharding import use_mesh
    from repro.training.optimizer import OptimizerConfig
    from repro.training.trainer import TrainConfig, init_train_state, train

    devs = jax.devices()
    if len(devs) != 4:
        raise AssertionError(f"--four-chips needs 4 devices, found "
                             f"{len(devs)}")
    arch = get_config(ARCH).replace(n_layers=TRAIN_LAYERS)
    pipe = SyntheticLM(DataConfig(global_batch=TRAIN_BATCH, seq_len=TRAIN_SEQ,
                                  vocab_size=arch.vocab_size, seed=seed))
    tcfg = TrainConfig(steps=TRAIN_STEPS, log_every=1,
                       opt=OptimizerConfig(lr=3e-4, warmup_steps=1,
                                           total_steps=TRAIN_STEPS))

    def mesh_of(devices):
        return Mesh(np.array(devices).reshape(len(devices), 1),
                    ("data", "model"), axis_types=(AxisType.Auto,) * 2)

    results = {}
    for label, devices in (("4 chips", devs), ("1 chip", devs[:1])):
        with use_mesh(mesh_of(devices)):
            if len(devices) == 4:
                params, opt_state, _, _ = init_train_state(arch, tcfg, seed)
                leaves = jax.tree.leaves((params, opt_state))
                n_sharded = 0
                for leaf in leaves:
                    if "data" not in str(leaf.sharding.spec):
                        continue
                    n_sharded += 1
                    for shard in leaf.addressable_shards:
                        if shard.data.size * 4 != leaf.size:
                            raise AssertionError(
                                f"leaf {leaf.shape} {leaf.sharding.spec}: "
                                f"device {shard.device} holds "
                                f"{shard.data.size} of {leaf.size}")
                log(f"[train] {n_sharded} of {len(leaves)} param/optimizer "
                    "leaves FSDP-sharded; each of the 4 chips holds a "
                    "quarter of every one")
                if n_sharded == 0:
                    raise AssertionError("no leaf is sharded over 'data'")
                del params, opt_state, leaves
            t0 = time.perf_counter()
            results[label] = train(arch, tcfg, pipe, seed=seed)
        log(f"[train] {label}: {TRAIN_STEPS} steps wall_s="
            f"{time.perf_counter() - t0:.3f} final metrics {results[label]}")
    d = abs(results["4 chips"]["loss"] - results["1 chip"]["loss"])
    log(f"[train] |loss(4 chips) - loss(1 chip)| = {d:.6g} "
        f"(bound {LOSS_ATOL})")
    if not np.isfinite(results["4 chips"]["loss"]) or d > LOSS_ATOL:
        raise AssertionError("four-chip and one-chip losses disagree")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train phase, on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run this script "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind}, "
              f"{len(jax.devices())} devices)", file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    log(f"[device] {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {cache}")
    phases = ((("train", train_phase),) if args.four_chips
              else (("kernel", kernel_phase), ("serve", serve_phase)))
    failed = []
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            phase(args.seed)
        except Exception:  # report, then run the next phase; exit 1 below
            traceback.print_exc()
            failed.append(name)
        stats = dev.memory_stats() or {}
        log(f"[{name}] {'FAILED' if name in failed else 'passed'} in "
            f"wall_s={time.perf_counter() - t0:.3f}; device "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
