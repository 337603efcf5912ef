"""Serving cells: the program's ``Engine`` and ``Scheduler`` driven by a
traffic mix for ``--seconds``, then checked against the plain reference.

Set-up makes the weights, warms every program the cell's traffic uses on a
throw-away engine, and builds a fresh engine and scheduler for the window.
The window drives ``Scheduler.step`` only. It submits each request at its
due time (an open loop) and, once the window closes, steps on until every
request that became due in it has its first token.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import List

import numpy as np

from chipbench import harness
from chipbench.traffic.generator import make_requests


@dataclasses.dataclass
class Track:
    """One request of the window, timed from its due time."""
    req: object
    due: float                      # absolute perf_counter seconds
    submitted: float
    times: List[float] = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return self.req.wall_finish is not None


class DispatchLog:
    """Records what the engine's compiled programs are given and choose,
    through the engine's per-instance dispatch seams, without a transfer:
    the arrays stay on the device until the window has closed. Before a
    lane is reset for its next request it also copies the lane's state, in
    every layer group of the cache, into a store made at set-up
    (``resets`` lanes deep, written in place, so the window allocates
    nothing), so that the check can replay each dispatch from the state
    the program had. A recurrent group (``kinds``, from the reference
    module) is overwritten by every token, so after each prefill chunk the
    prefilling lane's recurrent state is also copied, into a store
    ``chunks`` deep."""

    def __init__(self, engine, resets: int, chunks: int, kinds: dict):
        import jax
        import jax.numpy as jnp
        self.events = []
        prefill, decode, begin, reset = (
            engine._compiled_prefill, engine._compiled_decode,
            engine.begin_request, engine._reset_slot_state)
        groups = state_groups(engine.cache)
        recurrent = [g for g in groups if kinds[g] == "recurrent"]

        def zeros(tree, depth):
            return jax.tree.map(lambda a: jnp.zeros(
                (depth,) + a.shape[:1] + a.shape[2:], a.dtype), tree)

        def keep_lane_state(store, cache, slot, j):
            # a named function: the trace tells programs apart by name, and
            # the engine's prefill programs are anonymous lambdas
            return jax.tree.map(lambda s, a: s.at[j].set(
                jax.lax.dynamic_index_in_dim(a, slot, 1, False)),
                store, state_groups(cache))

        def keep_chunk_state(store, cache, lens, j):
            # the one lane that a prefill dispatch feeds, found on the device
            slot = jnp.argmax(lens > 0)
            now = state_groups(cache)
            return jax.tree.map(lambda s, a: s.at[j].set(
                jax.lax.dynamic_index_in_dim(a, slot, 1, False)),
                store, {g: now[g] for g in store})

        put = jax.jit(keep_lane_state, donate_argnums=(0,))
        self.store = put(zeros(groups, resets), engine.cache, 0, 0)
        self.chunks = None
        if recurrent:
            put_chunk = jax.jit(keep_chunk_state, donate_argnums=(0,))
            self.chunks = put_chunk(
                zeros({g: groups[g] for g in recurrent}, chunks),
                engine.cache, jnp.zeros(engine.cfg.batch_slots, jnp.int32),
                0)                                      # compiled now
        jax.block_until_ready((self.store, self.chunks))
        kept = [0, 0]

        def compiled_prefill(bucket):
            fn = prefill(bucket)

            def call(params, toks, cache, index, lens):
                out = fn(params, toks, cache, index, lens)
                self.events.append(("prefill", toks, index, lens, out[1]))
                if self.chunks is not None:
                    j = kept[1]
                    if j >= chunks:
                        raise RuntimeError(f"more than {chunks} prefill "
                                           f"chunks in the window")
                    self.chunks = put_chunk(self.chunks, out[2], lens, j)
                    self.events.append(("chunk", j))
                    kept[1] = j + 1
                return out
            return call

        def compiled_decode(sample):
            fn = decode(sample)

            def call(params, toks, cache, lengths, active, *rest):
                out = fn(params, toks, cache, lengths, active, *rest)
                self.events.append(("decode", toks, lengths, active, out[0]))
                return out
            return call

        def begin_request(prompt, *a, **kw):
            slot = begin(prompt, *a, **kw)
            self.events.append(("begin", slot, list(prompt)))
            return slot

        def reset_slot_state(slot):
            j = kept[0]
            if j >= resets:
                raise RuntimeError(f"more than {resets} lane resets in the "
                                   f"window")
            self.store = put(self.store, engine.cache, slot, j)
            self.events.append(("end", slot, j))
            kept[0] = j + 1
            reset(slot)

        engine._compiled_prefill = compiled_prefill
        engine._compiled_decode = compiled_decode
        engine.begin_request = begin_request
        engine._reset_slot_state = reset_slot_state

    def logged(self) -> list:
        """The events, each kept lane state as a ``replay.Snap`` of its
        store (read in place, not copied)."""
        from chipbench.reference.replay import Snap
        out = []
        for ev in self.events:
            if ev[0] == "end":
                ev = ("end", ev[1], Snap(self.store, ev[2], 0))
            elif ev[0] == "chunk":
                ev = ("chunk", Snap(self.chunks, ev[1], 0))
            out.append(ev)
        return out


def chunk_bound(reqs, serve: dict) -> int:
    """The most prefill chunks ``reqs`` can take: a prompt is cut at the
    budget and the largest bucket, and once more where it starts in a step
    whose budget another prompt has begun."""
    step = min(serve["prefill_token_budget"],
               serve.get("prefill_bucket_max", 1024))
    return sum(-(-len(r.prompt) // step) + 1 for r in reqs)


def buckets(serve: dict) -> List[int]:
    """Prefill buckets a scheduler with this budget can dispatch."""
    top = min(serve.get("prefill_bucket_max", 1024),
              serve["prefill_token_budget"])
    b, out = serve.get("prefill_bucket_min", 8), []
    while True:
        out.append(b)
        if b >= top:
            return out
        b *= 2


def engine_configs(serve: dict):
    from repro.serving.engine import ServeConfig
    from repro.serving.scheduler import SchedulerConfig
    return (ServeConfig(batch_slots=serve["batch_slots"],
                        max_ctx=serve["max_ctx"]),
            SchedulerConfig(prefill_token_budget=serve["prefill_token_budget"]))


def warm_up(arch, params, serve: dict, vocab: int, names: bool) -> dict:
    """Run every prefill bucket, the decode step and the lane reset once,
    on an engine that is then thrown away. Returns the HLO module names of
    the prefill and decode programs."""
    import jax
    from repro.serving.engine import Engine
    from repro.serving.params import SamplingParams
    from repro.serving.scheduler import Scheduler

    scfg, qcfg = engine_configs(serve)
    eng = Engine(arch, params, scfg)
    sched = Scheduler(eng, qcfg)
    rng = np.random.default_rng(0)
    sizes = buckets(serve)
    n = max(len(sizes), 2 * scfg.batch_slots)
    for i in range(n):
        sched.submit(rng.integers(0, vocab, sizes[i % len(sizes)]).tolist(),
                     params=SamplingParams(max_tokens=2))
    while not sched.idle():
        sched.step()
    jax.block_until_ready(eng.cache)
    modules = program_modules(eng, sizes) if names else {}
    del sched, eng
    gc.collect()
    return modules


def program_modules(eng, sizes) -> dict:
    """The module names of the prefill and decode programs, read from the
    programs that the warm-up ran (lowered again, not compiled)."""
    import jax
    import jax.numpy as jnp
    b = eng.cfg.batch_slots
    z = jnp.zeros((b,), jnp.int32)
    out = {"prefill": set(), "decode": set()}
    for s in sizes:
        low = eng._compiled_prefill(s).lower(
            eng.params, jnp.zeros((b, s), jnp.int32), eng.cache, z, z)
        out["prefill"].add(_module_of(low))
    low = eng._compiled_decode(False).lower(
        eng.params, jnp.zeros((b, 1), jnp.int32), eng.cache, z,
        jnp.zeros((b,), bool), jax.random.PRNGKey(0),
        jnp.zeros((b,), jnp.float32), z - 1, z)
    out["decode"].add(_module_of(low))
    if out["prefill"] & out["decode"]:
        raise RuntimeError(f"prefill and decode programs share a module "
                           f"name: {out}")
    return {k: sorted(v) for k, v in out.items()}


def _module_of(lowered) -> str:
    head = lowered.as_text().split("\n", 1)[0]
    return head.split("@", 1)[1].split()[0].strip('"')


def open_window(sched, reqs, seconds: float, drain_s: float):
    from repro.serving.params import SamplingParams
    tracks: List[Track] = []
    live: List[Track] = []
    lateness = []
    t0 = harness.now()
    i = 0
    while True:
        t = harness.now()
        while i < len(reqs) and reqs[i].due_s < seconds \
                and t0 + reqs[i].due_s <= t:
            due = t0 + reqs[i].due_s
            r = sched.submit(reqs[i].prompt, params=SamplingParams(
                max_tokens=reqs[i].max_tokens))
            tr = Track(r, due, harness.now())
            lateness.append(tr.submitted - due)
            tracks.append(tr)
            live.append(tr)
            i += 1
        closed = t - t0 >= seconds
        if closed and all(tr.req.wall_first is not None for tr in tracks):
            break       # every request due in the window has its first token
        if t - t0 >= seconds + drain_s:
            break
        if sched.idle():
            nxt = (t0 + reqs[i].due_s if i < len(reqs)
                   and reqs[i].due_s < seconds else t0 + seconds)
            with _span("wait_for_arrival"):
                time.sleep(max(0.0, min(nxt - harness.now(), 0.05)))
            continue
        with _span("scheduler_step"):
            sched.step()
        _stamp(live)
    return t0, tracks, lateness


def _span(name: str):
    """A host span in the profiler's trace (free when it is off)."""
    import jax
    return jax.profiler.TraceAnnotation("chipbench." + name)


def _stamp(live: List[Track]) -> None:
    """Time the tokens that the last scheduler step made visible."""
    t = harness.now()
    for tr in list(live):
        r = tr.req
        n = len(r.generated)
        while len(tr.times) < n:
            tr.times.append(r.wall_first if not tr.times else t)
        if tr.done:
            live.remove(tr)


def run(ctx) -> None:
    """One run of a serving cell; fills ``ctx`` with results."""
    _check(ctx, *window(ctx))


def window(ctx):
    """Set-up and the measured window; returns the weights and the
    dispatch log, with the engine freed."""
    import jax
    from repro.models import init_params
    from repro.serving.engine import Engine
    from repro.serving.scheduler import Scheduler

    from chipbench import reference, weights

    spec, settings, mix = ctx.spec, ctx.settings, ctx.mix
    serve = settings["serve"]
    arch = harness.arch_from_spec(spec)
    vocab = spec["arch"]["vocab_size"]
    kinds = reference.state_kinds(spec)
    params = weights.make_params(spec, ctx.seed)
    weights.check_layout(params, jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), arch)))
    ctx.modules = warm_up(arch, params, serve, vocab, names=ctx.trace)
    reqs = make_requests(mix, ctx.seed, vocab)
    scfg, qcfg = engine_configs(serve)
    engine = Engine(arch, params, scfg)
    # a lane is reset only as a request due in the window takes it
    due = [r for r in reqs if r.due_s < ctx.seconds]
    log = DispatchLog(engine, len(due), chunk_bound(due, serve), kinds)
    sched = Scheduler(engine, qcfg)
    jax.block_until_ready((params, engine.cache))
    compiles0 = ctx.compiles.n
    ctx.setup_s = harness.now() - ctx.t_start

    with ctx.tracing():
        t0, tracks, lateness = open_window(
            sched, reqs, ctx.seconds, settings.get("drain_s", 120.0))
        jax.block_until_ready(engine.cache)
        t1 = harness.now()
    ctx.window = (t0, t1)
    ctx.compiles_in_window = ctx.compiles.n - compiles0
    ctx.peak_bytes = harness.peak_bytes(jax.devices()[:1])
    ctx.engine_stats = dict(engine.stats)
    ctx.tracks = tracks
    _serve_metrics(ctx, tracks, lateness)

    events = log.logged()
    ctx.program_state = state_groups(engine.cache)
    del log, sched, engine
    gc.collect()
    return params, events


def state_groups(cache) -> dict:
    """Every layer group of the engine's cache as {group: {leaf: (layers,
    B, ...)}}: each group of the super-blocks as stacked, and each tail
    layer with a layer axis of its own."""
    out = dict(cache.get("superblocks", {}))
    for name, group in cache.get("tail", {}).items():
        out[name] = {k: a[None] for k, a in group.items()}
    return out


def _serve_metrics(ctx, tracks, lateness) -> None:
    t0, t1 = ctx.window
    seconds = ctx.seconds
    ttft = [(tr.req.wall_first - tr.due) * 1e3
            if tr.req.wall_first is not None else float("inf")
            for tr in tracks]
    gaps = [(b - a) * 1e3 for tr in tracks
            for a, b in zip(tr.times, tr.times[1:])]
    ctx.attempted = len(tracks)
    # a request fails when its first token never came
    ctx.failed = sum(1 for tr in tracks if tr.req.wall_first is None)
    generated = sum(sum(1 for t in tr.times if t <= t1) for tr in tracks)
    vals = {"setup_s": ctx.setup_s}
    if tracks:
        vals["ttft_p90_ms"] = harness.percentile(ttft, 0.90)
        vals["itl_p95_ms"] = harness.percentile(gaps, 0.95) if gaps \
            else float("nan")
    ctx.values = vals
    ctx.notes.append(
        f"window {t1 - t0:.3f} s (nominal {seconds}); requests {len(tracks)},"
        f" finished {sum(tr.done for tr in tracks)}, token gaps {len(gaps)},"
        f" generated {generated}, prefill tokens "
        f"{ctx.engine_stats['prefill_tokens']}, decode steps "
        f"{ctx.engine_stats['decode_steps']}, compiles in window "
        f"{ctx.compiles_in_window}")
    if lateness:
        ctx.notes.append(
            f"generator lateness: median {np.median(lateness) * 1e3:.3f} ms, "
            f"max {max(lateness) * 1e3:.3f} ms over {len(lateness)} submits")
    if tracks:
        ctx.notes.append(
            f"ttft_ms p50 {harness.percentile(ttft, 0.5):.1f} "
            f"p90 {harness.percentile(ttft, 0.9):.1f}; itl_ms p50 "
            f"{harness.percentile(gaps, 0.5) if gaps else float('nan'):.1f} "
            f"p95 {harness.percentile(gaps, 0.95) if gaps else float('nan'):.1f}")


def _check(ctx, params, events) -> None:
    """Check each lane's token stream, then replay the window's dispatches
    through the reference, each from the program's state before it, and
    compare every key and value the program wrote and every served
    token's logit with the reference's."""
    from chipbench.reference import load_model, replay
    from chipbench.reference.grmac import REFERENCE

    t = harness.now()
    ctx.log_events = events
    serve = ctx.settings["serve"]
    b = serve["batch_slots"]
    host = replay.host_events(events)
    w = replay.walk(host, b)
    faults = replay.stream_faults(
        w, [(tr.req.prompt, tr.req.generated) for tr in ctx.tracks])
    replay.attach_final_state(w, ctx.program_state)
    model = load_model(ctx.spec, params)
    del params
    res = replay.run(model, w, host, REFERENCE[ctx.spec["arch"]["dtype"]],
                     [replay.as_choices(w.served, b)], b, serve["max_ctx"])
    g = replay.gaps(res, w.served)
    n_tok = int(g.size)
    limits = ctx.spec["check"]
    ctx.checks += [
        harness.Check("served_tokens", float(n_tok), 1.0, floor=True),
        harness.Check("stream_faults", float(len(faults)), 0.0),
        harness.Check("max_logit_gap", float(g.max()) if n_tok
                      else float("inf"), float(limits["max_logit_gap"])),
        harness.Check("state_error_worst_layer", replay.worst(res.state_error),
                      float(limits["state_error_worst_layer"]))]
    ctx.notes += faults[:5]
    ctx.notes.append(
        f"reference: {n_tok} served tokens over {len(host)} logged "
        f"events in {harness.now() - t:.1f} s; mean logit gap "
        f"{g.mean() if n_tok else 0:.6g}; tokens off the reference argmax "
        f"{int(np.sum(g > 0))}; state error by layer "
        f"{[round(e, 6) for e in res.state_error]}")
    ctx.reference_s = harness.now() - t
