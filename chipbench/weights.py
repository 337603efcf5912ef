"""Random weights of a configuration, made on the device from ``--seed`` in
one jitted call, in the dtypes and tree layout that the program serves
(checked against a shape-only trace of the program's own initialiser).

The layers' tree comes from the configuration's reference module
(``param_specs``, see ``chipbench.reference``): each leaf is an init spec,
``(rows, cols, std)`` for a normal matrix, ``(n, kind)`` for a vector of a
kind drawn here (``gain`` around 1, ``bias`` around 0), or a function
``(key, lead, dtype) -> array`` for a leaf only that architecture has.
Embedding, final norm and an untied head are made here for every module.

Scales follow the usual initialisation: input projections N(0, 1/d_in),
output projections further divided by sqrt(2 * n_layers), embeddings
N(0, 0.02^2). Norm gains and biases are drawn around their customary
values, so that every term of the reference is exercised.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import reference


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def padded_vocab(v: int) -> int:
    return -(-v // 256) * 256


def _leaf(key, shape_spec, lead, dtype):
    """One array from its spec: (rows, cols, std) is a normal matrix;
    (n, kind) a vector of the named kind; a function draws its own."""
    if callable(shape_spec):
        return shape_spec(key, lead, dtype)
    if isinstance(shape_spec[-1], str):
        n, kind = shape_spec
        shape = lead + (n,)
        u = jax.random.normal(key, shape, jnp.float32)
        if kind == "gain":
            return (1.0 + 0.1 * u).astype(dtype)
        if kind == "bias":
            return (0.1 * u).astype(dtype)
        raise ValueError(kind)
    rows, cols, std = shape_spec
    return (std * jax.random.normal(key, lead + (rows, cols),
                                    jnp.float32)).astype(dtype)


def _is_spec(x) -> bool:
    return isinstance(x, tuple) or callable(x)


def make_params(spec: dict, seed: int):
    """The program's parameter tree for ``spec``, on the device."""
    a = spec["arch"]
    dtype = jnp.dtype(a["dtype"])
    superblock, tail = reference.module(spec).param_specs(a)
    sb_leaves, sb_def = jax.tree.flatten(superblock, is_leaf=_is_spec)
    tl_leaves, tl_def = jax.tree.flatten(tail, is_leaf=_is_spec)
    n_super = a["n_layers"] // len(a["block_pattern"])
    vp = padded_vocab(a["vocab_size"])

    def build(key):
        n = len(sb_leaves)
        ks = jax.random.split(key, n + len(tl_leaves) + 3)
        p = {"embed": (0.02 * jax.random.normal(
                 ks[-3], (vp, a["d_model"]), jnp.float32)).astype(dtype),
             "final_norm": {"g": _leaf(ks[-2], (a["d_model"], "gain"), (),
                                       dtype)}}
        if sb_leaves:
            p["superblocks"] = jax.tree.unflatten(sb_def, [
                _leaf(ks[i], s, (n_super,), dtype)
                for i, s in enumerate(sb_leaves)])
        if tl_leaves:
            p["tail"] = jax.tree.unflatten(tl_def, [
                _leaf(ks[n + i], s, (), dtype)
                for i, s in enumerate(tl_leaves)])
        if not a["tie_embeddings"]:
            p["lm_head"] = {"w": _leaf(
                ks[-1], (a["d_model"], vp, 1 / math.sqrt(a["d_model"])), (),
                dtype)}
        return p

    return jax.jit(build)(seed_key(seed))


def check_layout(params, program_shapes) -> None:
    """Raise unless ``params`` has the program's tree, shapes and dtypes."""
    mine = jax.tree.map(lambda x: (x.shape, str(x.dtype)), params)
    theirs = jax.tree.map(lambda x: (x.shape, str(x.dtype)), program_shapes)
    if mine != theirs:
        raise ValueError(f"weight layout differs from the program's:\n"
                         f"benchmark {mine}\nprogram   {theirs}")
