"""Plain float32 reference of a dense decoder with grouped-query attention
(Qwen2 layout: RMSNorm, QKV bias, rotary positions, SwiGLU MLP), every
projection and the head through the GR-MAC column of ``grmac.py``.

It runs one dispatch of a slot-batched server at a time, over all of the
dispatch's lanes and rows: the activation scale of every projection is the
absmax of the whole dispatch, so rows of other lanes set each lane's
quantisation grid, and the reference computes them too.

* ``prefill``: tokens (B, S) at per-lane offsets ``index``; lane ``b``
  writes its first ``lens[b]`` keys and values, every row attends to the
  cache slots at or before its position.
* ``decode``: one token per lane at ``index``; every lane writes and
  attends, and only ``active`` lanes keep the write.

Each step returns, per lane, the largest logit, its token, and the logits
of the tokens in ``served`` (rows of token ids), at the lane's last row,
and the cache with the step's own keys and values written. Keys and values
at a lane's positions below ``given`` are read from the incoming cache, not
from the step's own writes (but for the write of a lane that a decode
step leaves idle): a replay passes the served program's state there, so
that each position is computed from the same context as the program's.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference.grmac import (
    HIGHEST, Numerics, grmac, parse_grid, quantize_weight)

_NEG = -1e30


def rmsnorm(x, g, eps=1e-6):
    rms = jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return x / rms * g.astype(jnp.float32)


def rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def silu(x, act):
    """x / (1 + exp(-x)), rounded after each operation as the program's
    activation dtype rounds it (jax.nn.silu is exp, add, divide, multiply
    in that dtype)."""
    return act(x * act(1.0 / act(1.0 + act(jnp.exp(-x)))))


class Model:
    """Quantised weights and the jitted dispatch steps of one config."""

    def __init__(self, spec: dict, params: dict):
        self.spec = spec
        cim = spec["cim"]
        self.gx = parse_grid(cim["fmt_x"])
        self.gw = parse_grid(cim["fmt_w"])
        self.n_r, self.enob = cim["n_r"], float(cim["enob"])
        self.weights = jax.jit(self._prepare)(params)
        self._steps = {}

    def _prepare(self, params):
        q = jax.vmap(lambda w: quantize_weight(w, self.gw))
        blk = params["superblocks"]["b0_attn"]
        at, ff = blk["attn"], blk["ffn"]
        layers = {"g1": blk["norm1"]["g"], "g2": blk["norm2"]["g"]}
        for name, p in (("wq", at["wq"]), ("wk", at["wk"]), ("wv", at["wv"]),
                        ("wo", at["wo"]), ("wi", ff["wi"]), ("wg", ff["wg"]),
                        ("wd", ff["wo"])):
            layers[name] = q(p["w"])
            if "b" in p:
                layers["b" + name[1]] = p["b"]
        head = (params["embed"].T if self.spec["arch"]["tie_embeddings"]
                else params["lm_head"]["w"])
        return {"embed": params["embed"], "layers": layers,
                "final": params["final_norm"]["g"],
                "head": quantize_weight(head, self.gw)}

    def init_cache(self, batch: int, ctx: int):
        a = self.spec["arch"]
        shape = (a["n_layers"], batch, ctx, a["n_kv_heads"], a["d_head"])
        return {"k": jnp.zeros(shape, jnp.float32),
                "v": jnp.zeros(shape, jnp.float32)}

    def step(self, kind: str, numerics: Numerics, seq: int):
        key = (kind, numerics.name, seq)
        if key not in self._steps:
            self._steps[key] = jax.jit(self._make_step(kind, numerics),
                                       donate_argnums=(1,))
        return self._steps[key]

    def _dense(self, x, wsw, act, bias=None):
        lead = x.shape[:-1]
        wq, sw = wsw
        y = act(grmac(x.reshape(-1, x.shape[-1]), wq, sw, grid_x=self.gx,
                      n_r=self.n_r, enob=self.enob))
        y = y.reshape(*lead, -1)
        if bias is not None:
            y = act(y + bias.astype(jnp.float32))
        return y

    def _make_step(self, kind, numerics):
        a = self.spec["arch"]
        h, kv, dh = a["n_heads"], a["n_kv_heads"], a["d_head"]
        vocab, theta = a["vocab_size"], float(a["rope_theta"])
        act = numerics.act
        dense = self._dense

        def fn(w, cache, toks, index, lane_arg, served, given):
            b, s = toks.shape
            ctx = cache["k"].shape[2]
            slots = jnp.arange(ctx)[None, :]
            held = slots < given[:, None]
            if kind == "decode":
                # an idle lane's write is the step's own, kept only while
                # the step runs: it attends to it, and then drops it
                held &= (slots != index[:, None]) | lane_arg[:, None]
            held = held[:, :, None, None]
            x = act(w["embed"][toks].astype(jnp.float32))
            pos = index[:, None] + jnp.arange(s)[None, :]
            if kind == "prefill":
                valid = jnp.arange(s)[None, :] < lane_arg[:, None]
                tgt = jnp.where(valid, pos, ctx)
                mask = jnp.arange(ctx)[None, None, :] <= pos[:, :, None]
            else:
                tgt = jnp.clip(index, 0, ctx - 1)[:, None]
                mask = jnp.arange(ctx)[None, None, :] <= index[:, None, None]

            def layer(x, inp):
                lw, kc, vc = inp
                hn = act(rmsnorm(x, lw["g1"]))
                q = dense(hn, lw["wq"], act, lw.get("bq")).reshape(b, s, h, dh)
                k = dense(hn, lw["wk"], act, lw.get("bk")).reshape(b, s, kv, dh)
                v = dense(hn, lw["wv"], act, lw.get("bv")).reshape(b, s, kv, dh)
                q, k = act(rope(q, pos, theta)), act(rope(k, pos, theta))
                put = jax.vmap(lambda c, u, t: c.at[t].set(u, mode="drop"))
                kn, vn = put(kc, k, tgt), put(vc, v, tgt)
                ka, va = jnp.where(held, kc, kn), jnp.where(held, vc, vn)
                qg = q.reshape(b, s, kv, h // kv, dh)
                sc = jnp.einsum("bskgd,btkd->bkgst", qg, ka,
                                precision=HIGHEST) / math.sqrt(dh)
                sc = jnp.where(mask[:, None, None], sc, _NEG)
                pr = act(jax.nn.softmax(sc, axis=-1))
                o = jnp.einsum("bkgst,btkd->bskgd", pr, va, precision=HIGHEST)
                o = act(o.reshape(b, s, h * dh))
                x = act(x + dense(o, lw["wo"], act))
                h2 = act(rmsnorm(x, lw["g2"]))
                up = dense(h2, lw["wi"], act)
                mid = act(silu(dense(h2, lw["wg"], act), act) * up)
                x = act(x + dense(mid, lw["wd"], act))
                if kind == "decode":
                    keep = lane_arg[:, None, None, None]
                    kn, vn = jnp.where(keep, kn, kc), jnp.where(keep, vn, vc)
                return x, (kn, vn)

            x, (kn, vn) = jax.lax.scan(
                layer, x, (w["layers"], cache["k"], cache["v"]))
            xf = act(rmsnorm(x, w["final"]))
            sx = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12)
            last = (jnp.clip(lane_arg - 1, 0, s - 1) if kind == "prefill"
                    else jnp.zeros((b,), jnp.int32))
            rows = jnp.take_along_axis(xf, last[:, None, None], axis=1)[:, 0]
            wq, sw = w["head"]
            logits = grmac(rows, wq, sw, sx=sx, grid_x=self.gx, n_r=self.n_r,
                           enob=self.enob)[:, :vocab]
            logits = act(logits)
            top = jnp.max(logits, axis=-1)
            arg = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            at = jnp.take_along_axis(logits[None], served[..., None],
                                     axis=-1)[..., 0]
            return {"k": kn, "v": vn}, (top, arg, at)

        return fn

    def reset_lane(self, cache, lane: int):
        return jax.tree.map(lambda c: c.at[:, lane].set(0.0), cache)
