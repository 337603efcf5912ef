"""Random weights of a configuration, made on the device from ``--seed`` in
one jitted call, in the dtypes and tree layout that the program serves
(checked against a shape-only trace of the program's own initialiser).

Scales follow the usual initialisation: input projections N(0, 1/d_in),
output projections further divided by sqrt(2 * n_layers), embeddings
N(0, 0.02^2). Norm gains and biases are drawn around their customary
values, so that every term of the reference is exercised.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def padded_vocab(v: int) -> int:
    return -(-v // 256) * 256


def _dense_layer_shapes(a: dict) -> dict:
    d, h, kv, dh, f = (a["d_model"], a["n_heads"], a["n_kv_heads"],
                       a["d_head"], a["d_ff"])
    out_scale = 1.0 / math.sqrt(2 * a["n_layers"])
    attn = {"wq": {"w": (d, h * dh, 1 / math.sqrt(d))},
            "wk": {"w": (d, kv * dh, 1 / math.sqrt(d))},
            "wv": {"w": (d, kv * dh, 1 / math.sqrt(d))},
            "wo": {"w": (h * dh, d, out_scale / math.sqrt(h * dh))}}
    if a["qkv_bias"]:
        for k, n in (("wq", h * dh), ("wk", kv * dh), ("wv", kv * dh)):
            attn[k]["b"] = (n, "bias")
    return {"b0_attn": {
        "norm1": {"g": (d, "gain")},
        "attn": attn,
        "norm2": {"g": (d, "gain")},
        "ffn": {"wi": {"w": (d, f, 1 / math.sqrt(d))},
                "wg": {"w": (d, f, 1 / math.sqrt(d))},
                "wo": {"w": (f, d, out_scale / math.sqrt(f))}}}}


def _leaf(key, shape_spec, lead, dtype):
    """One array from its spec: (rows, cols, std) is a normal matrix;
    (n, kind) a vector of the named kind."""
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


def make_params(spec: dict, seed: int):
    """The program's parameter tree for ``spec``, on the device."""
    a = spec["arch"]
    dtype = jnp.dtype(a["dtype"])
    if tuple(a["block_pattern"]) != ("attn",):
        raise NotImplementedError("dense attention layers only")
    layer = _dense_layer_shapes(a)
    leaves, treedef = jax.tree.flatten(
        layer, is_leaf=lambda x: isinstance(x, tuple))
    vp = padded_vocab(a["vocab_size"])

    def build(key):
        ks = jax.random.split(key, len(leaves) + 3)
        lead = (a["n_layers"],)
        arrs = [_leaf(ks[i], s, lead, dtype) for i, s in enumerate(leaves)]
        p = {"embed": (0.02 * jax.random.normal(
                 ks[-3], (vp, a["d_model"]), jnp.float32)).astype(dtype),
             "superblocks": jax.tree.unflatten(treedef, arrs),
             "final_norm": {"g": _leaf(ks[-2], (a["d_model"], "gain"), (),
                                       dtype)}}
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
