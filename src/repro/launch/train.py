"""Production training launcher: builds the mesh, shards params/opt state,
and runs the resilient training loop. On this CPU container it runs the
local mesh; on a real cluster the same code runs under jax.distributed.

Usage:
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --reduced \
        --steps 20 --batch 8 --seq 128

``--drill`` runs the same configuration under the supervised fault-drill
harness instead: a seeded FaultPlan (kill / device loss / straggler) is
injected into the loop, failures are detected and recovered (freshest
checkpoint tier, elastic resume), and the run reports its GoodPut
partition and fault counters (see ``repro.training.supervisor``).

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \
        --reduced --steps 12 --drill --drill-faults 3
"""
import argparse
import json
import tempfile

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.parallel.sharding import use_mesh
from repro.training.fault import make_fault_plan
from repro.training.optimizer import OptimizerConfig
from repro.training.supervisor import DrillConfig, Supervisor, price_drill
from repro.training.trainer import TrainConfig, train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--cim", default="off")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--heartbeat-dir", default=None)
    ap.add_argument("--production-mesh", action="store_true",
                    help="16x16 mesh (needs 256 devices)")
    ap.add_argument("--drill", action="store_true",
                    help="run under the supervised fault-drill harness "
                         "(seeded kill/device-loss/straggler injection)")
    ap.add_argument("--drill-faults", type=int, default=3)
    ap.add_argument("--drill-seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    arch = get_config(args.arch)
    if args.reduced:
        arch = arch.reduced()
    if args.cim != "off":
        arch = arch.replace(cim=arch.cim.with_mode(args.cim))

    mesh = (make_production_mesh() if args.production_mesh
            else make_local_mesh())
    dcfg = DataConfig(global_batch=args.batch, seq_len=args.seq,
                      vocab_size=arch.vocab_size,
                      embedding_dim=arch.d_model
                      if arch.input_mode == "embeddings" else 0)
    tcfg = TrainConfig(
        steps=args.steps, microbatches=args.microbatches,
        ckpt_dir=args.ckpt_dir, log_every=5,
        opt=OptimizerConfig(lr=args.lr, warmup_steps=5,
                            total_steps=args.steps))
    if args.drill:
        plan = make_fault_plan(args.drill_seed, args.steps,
                               n_faults=args.drill_faults)
        with tempfile.TemporaryDirectory() as wd, use_mesh(mesh):
            drill_cfg = DrillConfig(workdir=args.ckpt_dir or wd,
                                    steps=args.steps)
            report = Supervisor(arch, tcfg, drill_cfg, SyntheticLM(dcfg),
                                plan, seed=args.drill_seed).run_drill()
        report["energy"] = price_drill(
            arch, report, tokens_per_step=args.batch * args.seq)
        report.pop("losses")
        print(json.dumps(report, indent=1))
        return

    with use_mesh(mesh):
        metrics = train(arch, tcfg, SyntheticLM(dcfg),
                        heartbeat_dir=args.heartbeat_dir)
    print("final:", metrics)


if __name__ == "__main__":
    main()
