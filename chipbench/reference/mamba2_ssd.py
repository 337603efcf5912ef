"""Plain float32 reference of Mamba2 (arXiv:2405.21060, attention-free
SSD layers) in the program's layout, every projection and the head
through the GR-MAC column of ``grmac.py``.

A layer is a pre-norm residual block. From the normed input u, three
projections give the gate z and the input x (``in_proj``), B and C
(``bc_proj``, one group) and the step size dt (``dt_proj``, one a head);
x passes a depthwise causal convolution of ``conv_width`` taps and SiLU;
then, per head h of ``ssm_headdim`` channels, with a = exp(-dt A_h),

    H_t = a_t H_{t-1} + dt_t B_t x_tᵀ        (d_state x headdim)
    y_t = C_tᵀ H_t + D_h x_t

written out one token at a time (a ``lax.scan``), and y, gated by
SiLU(z) and RMS-normed, goes through ``out_proj`` into the residual.
The configuration file lists where this layout departs from the
published one (``assumed``).

Like ``dense_gqa`` it replays one dispatch of the slot-batched server at a
time, over all of the dispatch's lanes and rows, since the activation
scale of every projection is the absmax of the whole dispatch:

* ``prefill``: tokens (B, S); lane ``b`` feeds its first ``lens[b]``
  tokens and keeps its state past them; every other row is computed from
  its lane's state and dropped, as the program computes it.
* ``decode``: one token a lane; every lane computes, only ``active`` lanes
  keep their new state.

The cache is one recurrent group a layer: the SSD state ``h`` (B, heads,
d_state, headdim) and the convolution's last ``conv_width - 1`` inputs,
both float32 as the program keeps them.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference.dense_gqa import Model as _DenseModel
from chipbench.reference.dense_gqa import rmsnorm, silu
from chipbench.reference.grmac import HIGHEST, grmac, quantize_weight

STATE_KINDS = {"ssm": "recurrent"}
GROUP = "b0_ssm"
DT_RANGE = (1e-3, 1e-1)        # the published dt_min, dt_max
A_RANGE = (1.0, 16.0)          # the published A_init_range


def _dims(a: dict) -> dict:
    di = a["ssm_expand"] * a["d_model"]
    return {"d": a["d_model"], "di": di, "n": a["ssm_state"],
            "p": a["ssm_headdim"], "nh": di // a["ssm_headdim"],
            "w": a["conv_width"]}


def _ssm_only(a: dict) -> None:
    if tuple(a["block_pattern"]) != ("ssm",):
        raise ValueError(f"mamba2_ssd covers SSD layers only, not "
                         f"{a['block_pattern']}")


def _a_log(nh):
    """log A, A uniform over the published range (float32, per head)."""
    def draw(key, lead, dtype):
        return jnp.log(jax.random.uniform(key, lead + (nh,), jnp.float32,
                                          *A_RANGE))
    return draw


def _dt_bias(nh):
    """softplus⁻¹(dt), dt log-uniform over the published range (float32,
    per head)."""
    def draw(key, lead, dtype):
        lo, hi = (math.log(v) for v in DT_RANGE)
        dt = jnp.exp(jax.random.uniform(key, lead + (nh,), jnp.float32,
                                        lo, hi))
        return dt + jnp.log(-jnp.expm1(-dt))
    return draw


def _skip(nh):
    """D around its published 1 (float32, per head)."""
    def draw(key, lead, dtype):
        return 1.0 + 0.1 * jax.random.normal(key, lead + (nh,), jnp.float32)
    return draw


def param_specs(a: dict):
    """One layer of the program's tree (the pattern is one "ssm" layer, so
    there is no tail), each leaf's init spec (``weights._leaf``)."""
    _ssm_only(a)
    k = _dims(a)
    d, di, nh = k["d"], k["di"], k["nh"]
    out_scale = 1.0 / math.sqrt(2 * a["n_layers"])
    ssm = {"in_proj": {"w": (d, 2 * di, 1 / math.sqrt(d))},
           "bc_proj": {"w": (d, 2 * k["n"], 1 / math.sqrt(d))},
           "dt_proj": {"w": (d, nh, 1 / math.sqrt(d))},
           "conv": (k["w"], di, 1 / math.sqrt(k["w"])),
           "A_log": _a_log(nh),
           "D": _skip(nh),
           "dt_bias": _dt_bias(nh),
           "out_norm": {"g": (di, "gain")},
           "out_proj": {"w": (di, d, out_scale / math.sqrt(di))}}
    return {GROUP: {"norm1": {"g": (d, "gain")}, "ssm": ssm}}, {}


def projection_weights(a: dict) -> int:
    """Weights of every projection and the head (one token's matmuls)."""
    _ssm_only(a)
    k = _dims(a)
    d, di = k["d"], k["di"]
    layer = d * (2 * di + 2 * k["n"] + k["nh"]) + di * d
    return a["n_layers"] * layer + d * a["vocab_size"]


def mixer_flops(a: dict, start: int, count: int) -> float:
    """The SSD update and readout, whatever the position: the state's
    decay and rank-one input (two multiply-adds an element of
    d_inner x d_state) and its readout (one), 6 FLOPs an element in every
    layer. The convolution, gates and norms are not counted, as norms and
    softmax are not for attention."""
    k = _dims(a)
    return 6.0 * a["n_layers"] * k["di"] * k["n"] * count


def call_widths(a: dict) -> list:
    """Output widths of the projections (z and x, B and C, dt, out) and
    the head."""
    _ssm_only(a)
    k = _dims(a)
    return [2 * k["di"], 2 * k["n"], k["nh"], k["d"], a["vocab_size"]]


class Model(_DenseModel):
    """Quantised weights and the jitted dispatch steps of one config."""

    def _prepare(self, params):
        q = jax.vmap(lambda w: quantize_weight(w, self.gw))
        blk = params["superblocks"][GROUP]
        s = blk["ssm"]
        layers = {"g1": blk["norm1"]["g"], "g_out": s["out_norm"]["g"],
                  "w_in": q(s["in_proj"]["w"]), "w_bc": q(s["bc_proj"]["w"]),
                  "w_dt": q(s["dt_proj"]["w"]),
                  "w_out": q(s["out_proj"]["w"]), "conv": s["conv"],
                  "a_log": s["A_log"], "d": s["D"], "dt_bias": s["dt_bias"]}
        head = (params["embed"].T if self.spec["arch"]["tie_embeddings"]
                else params["lm_head"]["w"])
        return {"embed": params["embed"], "layers": layers,
                "final": params["final_norm"]["g"],
                "head": quantize_weight(head, self.gw)}

    def init_cache(self, batch: int, ctx: int):
        a = self.spec["arch"]
        k = _dims(a)
        lead = (a["n_layers"], batch)
        return {GROUP: {
            "h": jnp.zeros(lead + (k["nh"], k["n"], k["p"]), jnp.float32),
            "conv": jnp.zeros(lead + (k["w"] - 1, k["di"]), jnp.float32)}}

    def _make_step(self, kind, numerics):
        a = self.spec["arch"]
        k = _dims(a)
        di, n, nh, p = k["di"], k["n"], k["nh"], k["p"]
        vocab = a["vocab_size"]
        act = numerics.act
        dense = self._dense

        def recur(lw, carry, t_in):
            """One token of every lane: the convolution over the kept
            inputs and this one, the state's update and its readout."""
            h, win = carry
            x_t, b_t, c_t, dt_t, keep = t_in
            b = x_t.shape[0]
            full = jnp.concatenate([win, x_t[:, None, :]], axis=1)
            xc = jnp.sum(full * lw["conv"].astype(jnp.float32)[None], axis=1)
            xh = jax.nn.silu(xc).reshape(b, nh, p)
            decay = jnp.exp(-dt_t * jnp.exp(lw["a_log"])[None, :])
            h_new = (decay[:, :, None, None] * h
                     + dt_t[:, :, None, None] * b_t[:, None, :, None]
                     * xh[:, :, None, :])
            y = jnp.einsum("bn,bhnp->bhp", c_t, h_new, precision=HIGHEST)
            y = y + lw["d"][None, :, None] * xh
            h = jnp.where(keep[:, None, None, None], h_new, h)
            win = jnp.where(keep[:, None, None], full[:, 1:], win)
            return (h, win), y.reshape(b, di)

        def fn(w, state, toks, index, lane_arg, served, given):
            st = state[GROUP]
            b, s = toks.shape
            x = act(w["embed"][toks].astype(jnp.float32))
            if kind == "prefill":
                keep = jnp.arange(s)[None, :] < lane_arg[:, None]
            else:
                keep = jnp.broadcast_to(lane_arg[:, None], (b, s))

            def layer(x, inp):
                lw, h, win = inp
                u = act(rmsnorm(x, lw["g1"]))
                zx = dense(u, lw["w_in"], act)
                z, xin = zx[..., :di], zx[..., di:]
                bc = dense(u, lw["w_bc"], act)
                dt = jax.nn.softplus(dense(u, lw["w_dt"], act)
                                     + lw["dt_bias"][None, None, :])
                seq = tuple(jnp.moveaxis(t, 1, 0) for t in (
                    xin, bc[..., :n], bc[..., n:], dt, keep))
                (h, win), ys = jax.lax.scan(
                    lambda c, t: recur(lw, c, t), (h, win), seq)
                y = act(jnp.moveaxis(ys, 0, 1))
                gated = act(y * silu(z, act))
                yn = act(rmsnorm(gated, lw["g_out"]))
                x = act(x + dense(yn, lw["w_out"], act))
                return x, (h, win)

            x, (h, win) = jax.lax.scan(
                layer, x, (w["layers"], st["h"], st["conv"]))
            xf = act(rmsnorm(x, w["final"]))
            sx = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12)
            last = (jnp.clip(lane_arg - 1, 0, s - 1) if kind == "prefill"
                    else jnp.zeros((b,), jnp.int32))
            rows = jnp.take_along_axis(xf, last[:, None, None], axis=1)[:, 0]
            wq, sw = w["head"]
            logits = act(grmac(rows, wq, sw, sx=sx, grid_x=self.gx,
                               n_r=self.n_r, enob=self.enob)[:, :vocab])
            top = jnp.max(logits, axis=-1)
            arg = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            at = jnp.take_along_axis(logits[None], served[..., None],
                                     axis=-1)[..., 0]
            return {GROUP: {"h": h, "conv": win}}, (top, arg, at)

        return fn
