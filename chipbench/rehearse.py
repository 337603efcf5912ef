"""Compile a cell's programs for a described TPU v5e, without the chip, and
print what each needs of the device's memory.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --workload <cell>
    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --train <config> \\
        --seq 2048 --batches 4,8,16

A serving cell compiles its prefill buckets and its decode step on one chip
of a described ``v5e:2x2``; ``--train`` compiles the FSDP train step of a
configuration (in fakequant mode) on the whole (data 4, model 1) mesh for
each global batch. Nothing runs, so this says nothing about time; a
program that does not fit, or that the chip's compiler refuses, fails
here. The GR-MAC backend is pinned to the Pallas kernel, which is what
``auto`` picks on the chip.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import harness  # noqa: E402

GIB = 2 ** 30


def _describe(mem) -> str:
    return (f"args {mem.argument_size_in_bytes / GIB:.3f} GiB, out "
            f"{mem.output_size_in_bytes / GIB:.3f} GiB, temp "
            f"{mem.temp_size_in_bytes / GIB:.3f} GiB, alias "
            f"{mem.alias_size_in_bytes / GIB:.3f} GiB")


def topology():
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


def serve_programs(name: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.models import init_cache, init_params
    from repro.serving.engine import _decode_fn, _prefill_fn

    from chipbench import serve
    cell = harness.load_cell(name)
    serve_cfg = cell["settings"]["serve"]
    arch = harness.arch_from_spec(cell["spec"])
    arch = arch.replace(cim=arch.cim.with_backend("pallas"))
    one = SingleDeviceSharding(topology().devices[0])

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    b, ctx = serve_cfg["batch_slots"], serve_cfg["max_ctx"]
    params = shaped(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), arch)))
    cache = shaped(jax.eval_shape(
        lambda: init_cache(arch, b, ctx, jnp.float32)))

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape or (b,), dtype, sharding=one)

    for s in serve.buckets(serve_cfg):
        t = time.perf_counter()
        c = _prefill_fn(arch, s).lower(
            params, vec(jnp.int32, b, s), cache, vec(jnp.int32),
            vec(jnp.int32)).compile()
        print(f"{name} prefill bucket {s}: {_describe(c.memory_analysis())}"
              f"; compiled in {time.perf_counter() - t:.1f} s", flush=True)
    t = time.perf_counter()
    c = _decode_fn(arch, False).lower(
        params, vec(jnp.int32, b, 1), cache, vec(jnp.int32), vec(bool),
        vec(jnp.uint32, 2), vec(jnp.float32), vec(jnp.int32),
        vec(jnp.int32)).compile()
    print(f"{name} decode: {_describe(c.memory_analysis())}; compiled in "
          f"{time.perf_counter() - t:.1f} s", flush=True)


def train_step(config: str, seq: int, batches) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.models import init_params
    from repro.parallel.sharding import (
        batch_axes, named_sharding_tree, param_specs, use_mesh)
    from repro.training.optimizer import OptimizerConfig, init_opt_state
    from repro.training.trainer import TrainConfig, make_train_step

    spec = harness.load_json(harness.BENCH_DIR / "configs"
                             / f"{config}.json")
    arch = harness.arch_from_spec(spec)
    arch = arch.replace(cim=arch.cim.with_mode("fakequant"))
    topo = topology()
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    tcfg = TrainConfig(opt=OptimizerConfig())
    with use_mesh(mesh):
        key = jax.random.PRNGKey(0)
        p_shape = jax.eval_shape(lambda k: init_params(k, arch), key)
        o_shape = jax.eval_shape(lambda p: init_opt_state(p, tcfg.opt),
                                 p_shape)
        p_sh = named_sharding_tree(param_specs(p_shape, mesh), mesh)
        o_sh = named_sharding_tree(param_specs(o_shape, mesh), mesh)
        b_sh = NamedSharding(mesh, P(batch_axes(mesh)))

        def shaped(tree, sh):
            return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=s), tree, sh)

        step = jax.jit(make_train_step(arch, tcfg),
                       in_shardings=(p_sh, o_sh, b_sh),
                       out_shardings=(p_sh, o_sh, NamedSharding(mesh, P())))
        for gb in batches:
            batch = {"inputs": jax.ShapeDtypeStruct((gb, seq), jnp.int32,
                                                    sharding=b_sh),
                     "labels": jax.ShapeDtypeStruct((gb, seq), jnp.int32,
                                                    sharding=b_sh),
                     "mask": jax.ShapeDtypeStruct((gb, seq), jnp.float32,
                                                  sharding=b_sh)}
            t = time.perf_counter()
            try:
                c = step.lower(shaped(p_shape, p_sh), shaped(o_shape, o_sh),
                               batch).compile()
            except Exception as e:  # the compiler's refusal is the finding
                print(f"{config} train batch {gb}x{seq}: refused: "
                      f"{str(e).splitlines()[0][:300]}", flush=True)
                continue
            print(f"{config} train batch {gb}x{seq} on 4 chips: per device "
                  f"{_describe(c.memory_analysis())}; compiled in "
                  f"{time.perf_counter() - t:.1f} s", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--train")
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--batches", default="8")
    args = ap.parse_args(argv)
    harness.strict_precision()
    harness.import_program()
    if args.workload:
        serve_programs(args.workload)
    if args.train:
        train_step(args.train, args.seq,
                   [int(b) for b in args.batches.split(",")])
    return 0


if __name__ == "__main__":
    sys.exit(main())
