"""Replays a served window's dispatches through a plain reference, one
dispatch at a time, and reads how far the program's writes and served
tokens lie from the reference's.

The log holds, in order, what the server's compiled programs were given
and what they chose, and each lane's state when it was handed on:

    ("begin",   slot, prompt)                      a lane starts a request
    ("end",     slot, state)                       the lane's state (every
                                                   layer's K/V) just before
                                                   the next request resets it
    ("prefill", toks (B,S), index (B,), lens (B,), ids (B,S))
    ("decode",  toks (B,1), index (B,), active (B,), ids (B,))

A lane's prefill that reaches its prompt's length serves the token chosen
at its last row; a decode serves a token on every active lane.

Each dispatch is replayed from the program's own state: the keys and
values every lane had written, as the program wrote them (taken from the
lane's state when it was handed on, or after the window), including those
the dispatch itself writes, which the reference reads in place of its own
when it attends. So a rounding difference at one position does not spread
to later ones, and every key and value the program wrote in the window is
compared with what the reference computes from the same inputs and the
same context. Only the residual stream of one position, from layer to
layer, is the reference's own.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def host_events(log: list) -> list:
    """The log with every dispatch's arrays on the host (after the
    window); a lane's state stays on the device."""
    out = []
    for ev in log:
        if ev[0] in ("begin", "end"):
            out.append(ev)
        else:
            out.append((ev[0],) + tuple(np.asarray(a) for a in ev[1:]))
    return out


@dataclasses.dataclass
class Stint:
    """One request's time on one lane."""
    slot: int
    prompt: List[int]
    served: List[int] = dataclasses.field(default_factory=list)
    state: Optional[dict] = None        # its K/V when handed on
    claimed: bool = False


@dataclasses.dataclass
class Walk:
    """What the log says each lane was fed and served."""
    faults: List[str]
    stints: List[Stint]
    # {event index: (lanes, token ids)} of every token served
    served: Dict[int, Tuple[np.ndarray, np.ndarray]]
    # {event index: (written before, written after)}: each lane's count of
    # positions whose keys and values the program has written
    spans: Dict[int, Tuple[np.ndarray, np.ndarray]]
    # {begin event index: stint}
    begins: Dict[int, Stint]


def walk(events: list, batch: int) -> Walk:
    """Follow each lane's token stream through the log.

    A lane's prefill chunks must be its prompt, in order, with no gap or
    repeat, and each decode on an active lane must feed the token served
    just before it, at the next position; each departure is a fault."""
    faults: List[str] = []
    lanes: Dict[int, Stint] = {}
    fed = np.zeros(batch, np.int64)      # positions written, per lane
    done = np.zeros(batch, bool)         # the prompt is all in
    last = np.zeros(batch, np.int64)     # the token served last
    stints: List[Stint] = []
    served, spans, begins = {}, {}, {}
    for i, ev in enumerate(events):
        if ev[0] == "begin":
            b = int(ev[1])
            st = Stint(b, list(ev[2]))
            lanes[b] = st
            stints.append(st)
            begins[i] = st
            fed[b], done[b] = 0, False
            continue
        if ev[0] == "end":
            b = int(ev[1])
            if b in lanes:
                lanes[b].state = ev[2]
            continue
        before = fed.copy()
        if ev[0] == "prefill":
            toks, index, lens, ids = ev[1:5]
            lanes_out, ids_out = [], []
            for b in np.flatnonzero(lens > 0):
                st, n = lanes.get(int(b)), int(lens[b])
                if st is None or done[b]:
                    faults.append(f"event {i}: prefill on lane {b}, which "
                                  f"has no prompt to prefill")
                    continue
                f = int(fed[b])
                if int(index[b]) != f or toks[b, :n].tolist() != \
                        st.prompt[f:f + n]:
                    faults.append(f"event {i}: lane {b} prefilled tokens "
                                  f"{int(index[b])}..{int(index[b]) + n} "
                                  f"that are not its prompt's next {n}")
                fed[b] = int(index[b]) + n
                if fed[b] >= len(st.prompt):
                    done[b] = True
                    last[b] = int(ids[b, n - 1])
                    st.served.append(int(last[b]))
                    lanes_out.append(int(b))
                    ids_out.append(int(last[b]))
            if lanes_out:
                served[i] = (np.array(lanes_out), np.array(ids_out))
        else:
            toks, index, active, ids = ev[1:5]
            act = np.flatnonzero(active)
            for b in act:
                st = lanes.get(int(b))
                if st is None or not done[b]:
                    faults.append(f"event {i}: decode on lane {b} before "
                                  f"its prompt was prefilled")
                    continue
                if int(toks[b, 0]) != last[b] or int(index[b]) != fed[b]:
                    faults.append(
                        f"event {i}: lane {b} decoded token {int(toks[b, 0])}"
                        f" at {int(index[b])}, not its last served token "
                        f"{int(last[b])} at {int(fed[b])}")
                fed[b] = int(index[b]) + 1
                last[b] = int(ids[b])
                st.served.append(int(last[b]))
            if act.size:
                served[i] = (act, ids[act])
        spans[i] = (before, fed.copy())
    return Walk(faults, stints, served, spans, begins)


def stream_faults(w: Walk, requests) -> List[str]:
    """The walk's faults, and every request whose tokens, over every lane
    it held, are not what the server handed it (``requests``: (prompt,
    generated) pairs), or a lane that served a prompt no request sent."""
    faults = list(w.faults)
    for prompt, generated in requests:
        got: List[int] = []
        for st in w.stints:
            if not st.claimed and st.prompt == list(prompt) + got:
                got += st.served
                st.claimed = True
        if got != list(generated):
            faults.append(f"a request of {len(prompt)} prompt tokens was "
                          f"handed {len(generated)} tokens; its lanes "
                          f"served {len(got)}, and they differ")
    faults += [f"a lane served a prompt of {len(st.prompt)} tokens that no "
               f"request sent" for st in w.stints if not st.claimed]
    return faults


def attach_final_state(w: Walk, final: dict) -> None:
    """Give each stint still on its lane after the window that lane's
    state: ``final`` is {name: (layers, B, ...)} on the device."""
    for st in w.stints:
        if st.state is None:
            st.state = {k: v[:, st.slot] for k, v in final.items()}


@jax.jit
def _set_lane(history, slot, state):
    return jax.tree.map(lambda h, s: h.at[:, slot].set(s.astype(h.dtype)),
                        history, state)


@jax.jit
def _context(history, written):
    """Each lane's state around a dispatch: what it has written, zeros
    after it (as a lane's cache is after a reset)."""
    def f(h):
        pos = jnp.arange(h.shape[2])
        keep = pos[None, :] < written[:, None]              # (B, ctx)
        return jnp.where(keep[None, :, :, None, None], h, 0.0)
    return jax.tree.map(f, history)


@jax.jit
def _written_error(new, against, lo, hi):
    """Per layer, the squared distance of ``new`` from ``against`` and the
    squared size of ``against``, over each lane's positions lo..hi-1."""
    num = den = 0.0
    for name in new:
        pos = jnp.arange(new[name].shape[2])
        m = ((pos[None, :] >= lo[:, None]) & (pos[None, :] < hi[:, None]))
        m = m[None, :, :, None, None]
        a = against[name].astype(jnp.float32)
        d = jnp.where(m, new[name].astype(jnp.float32) - a, 0.0)
        num = num + jnp.sum(jnp.square(d), axis=(1, 2, 3, 4))
        den = den + jnp.sum(jnp.where(m, jnp.square(a), 0.0),
                            axis=(1, 2, 3, 4))
    return num, den


@dataclasses.dataclass
class Result:
    """Per served event, the reference's best logit, its argmax and the
    logits of each choice; per layer, the relative distance of the keys
    and values the program wrote from the reference's (and of each
    ``other`` numerics' from the reference's)."""
    tokens: Dict[int, dict]
    state_error: List[float]
    other_state_error: List[List[float]]


def run(model, w: Walk, events: list, numerics, choices: List[Dict[int,
        np.ndarray]], batch: int, ctx: int, others=()) -> Result:
    """Drive ``model`` through ``events`` in ``numerics``, each dispatch
    from the program's state. ``choices`` are sets of token
    choices ({event index: (B,) ids}) whose logits are read; ``others``
    are further numerics run from the same context: their writes are
    compared with the reference's, and their first choices are read after
    ``choices`` (as choice ``len(choices) + j``)."""
    history = model.init_cache(batch, ctx)
    zeros = np.zeros(batch, np.int32)
    pending = {}
    n_l = next(iter(history.values())).shape[0]
    acc = [jnp.zeros((2, n_l), jnp.float32) for _ in range(1 + len(others))]
    for i, ev in enumerate(events):
        if ev[0] == "begin":
            history = _set_lane(history, jnp.int32(ev[1]),
                                w.begins[i].state)
            continue
        if ev[0] == "end":
            continue
        kind, toks, index, lane_arg = ev[0], ev[1], ev[2], ev[3]
        lo, hi = (jnp.asarray(a, jnp.int32) for a in w.spans[i])
        args = (jnp.asarray(toks, jnp.int32), jnp.asarray(index, jnp.int32),
                jnp.asarray(lane_arg))
        outs, first = [], []
        for other in others:
            fn = model.step(kind, other, toks.shape[1])
            new, (_, arg, _) = fn(model.weights, _context(history, hi),
                                  *args, jnp.zeros((1, batch), jnp.int32), hi)
            outs.append(new)
            first.append(arg)
        served = jnp.asarray(np.stack([c.get(i, zeros) for c in choices])
                             .astype(np.int32))
        if first:
            served = jnp.concatenate([served, jnp.stack(first)])
        fn = model.step(kind, numerics, toks.shape[1])
        new, res = fn(model.weights, _context(history, hi), *args, served,
                      hi)
        num, den = _written_error(new, history, lo, hi)
        acc[0] = acc[0] + jnp.stack([num, den])
        for j, o in enumerate(outs):
            num, den = _written_error(o, new, lo, hi)
            acc[1 + j] = acc[1 + j] + jnp.stack([num, den])
        if i in w.served:
            pending[i] = res
    tokens = {i: {"top": t, "arg": a, "at": at}
              for i, (t, a, at) in jax.device_get(pending).items()}
    errs = [_relative(a) for a in jax.device_get(acc)]
    return Result(tokens, errs[0], errs[1:])


def _relative(acc: np.ndarray) -> List[float]:
    num, den = np.asarray(acc, np.float64)
    return [float(np.sqrt(n / d)) if d > 0 else 0.0 for n, d in zip(num, den)]


def worst(errors: List[float]) -> float:
    """The largest error; a layer that gave no number (NaN) counts as
    infinitely far."""
    return max((e if e == e else float("inf")) for e in errors)


def as_choices(served: Dict[int, Tuple[np.ndarray, np.ndarray]],
               batch: int) -> Dict[int, np.ndarray]:
    out = {}
    for i, (lanes, ids) in served.items():
        row = np.zeros(batch, np.int32)
        row[lanes] = ids
        out[i] = row
    return out


def gaps(result: Result, served, which: int = 0) -> np.ndarray:
    """Reference best logit minus the reference logit of choice ``which``,
    for every served token."""
    vals = []
    for i, (lanes, _) in served.items():
        r = result.tokens[i]
        vals.append(r["top"][lanes] - r["at"][which][lanes])
    return np.concatenate(vals) if vals else np.zeros(0)
