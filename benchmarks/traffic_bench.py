"""Open-loop traffic serving benchmark: goodput vs arrival rate, per arch.

Extends ``serve_bench``'s fixed-batch TTFT measurement to the numbers a
capacity planner needs: seeded Poisson arrivals at swept request rates,
uniform prompt/output length distributions, served through the
continuous-batching ``Scheduler`` AND the naive blocking-admission
``StaticBatchScheduler`` baseline (classic static batching) on the same
traffic. Reports, per (arch, rate, mode):

* goodput — completed tokens per unit time counting only requests whose
  TTFT met the SLO (``goodput_tok_per_step`` in deterministic virtual
  dispatch-units; ``goodput_tok_s`` in wall time);
* TTFT / TPOT p50 and p99 (wall ms, machine-dependent; TTFT also in
  virtual units);
* queue depth (max / mean) and the dispatch / completion counts;
* decode-phase pJ/token of the arch under the GR-CIM path (ledger-
  derived, as in serve_bench — the benchmark engines themselves serve
  digital so the timing numbers measure the scheduler, not the
  simulator).

Determinism contract (the CI gate): scheduling runs on the virtual
``StepClock`` (one unit per compiled dispatch), so admission order,
chunk slicing, dispatch counts, completion counts and virtual-time SLO
attainment are pure functions of the seeded traffic — those leaves are
compared with EXACT equality by benchmarks/compare.py. Wall-clock
latency leaves get the usual ratio + noise-floor gates. Termination is
by ``max_new_tokens`` only (no EOS), so token *values* never influence
the schedule and the counts hold across machines and XLA versions.

The per-arch sweep derives a **saturation knee**: the first swept rate
where marginal goodput per marginal offered load drops below 0.5 (the
service saturates; queueing takes over). Above capacity the continuous
scheduler must sustain strictly higher goodput than static batching —
recorded as the exact-gated ``beats_static_above_capacity`` leaf.

The record also embeds the scheduler-layer invariant counters
(``repro.analysis.invariants.run_scheduler_invariants``): compile budget
and one-transfer-per-decode-step proven under interleaving, in the same
record the latency numbers come from.

Shared-prefix mode (``--shared-prefix``, the ``prefix_bench`` records):
seeded Zipf draws over a small system-prompt pool, served cache-off and
cache-on (``repro.serving.prefix_cache``) on identical traffic. The
cache must win TTFT p50 and prefill dispatches at *bit-identical*
outputs — those booleans, the hit/miss/insert/evict/bytes counters, and
the tokens-dispatched counts are exact-gated; the prefill pJ/output
token is ledger-priced from tokens actually dispatched, so the hit rate
surfaces as a measured energy reduction. Satellite cells rerun the same
traffic under shortest-prompt admission (anti-starvation age bound at
the SLO) and under the closed-loop fixed-concurrency client model
(``run_closed_loop``).

Run:  PYTHONPATH=src python -m benchmarks.traffic_bench [--smoke]
          [--shared-prefix]
"""
import argparse
import time

import jax

from repro.configs import get_config
from repro.core import costs
from repro.models import init_params
from repro.serving.engine import Engine, ServeConfig
from repro.serving.scheduler import (
    Scheduler,
    SchedulerConfig,
    StaticBatchScheduler,
    StepClock,
    run_closed_loop,
    run_open_loop,
    synth_shared_prefix_traffic,
    synth_traffic,
)
from benchmarks.common import emit, save_json
from repro.compile_cache import enable_compile_cache

# attention KV and SSM recurrent-state cache families: the two extremes
# of per-slot state the scheduler juggles (serve_bench covers all four
# families; the traffic sweep keeps two so the rate grid stays wide)
ARCHS = [
    ("attn", "qwen2-1.5b"),
    ("ssm", "mamba2-1.3b"),
]
# offered load as fractions of the estimated saturation rate: two below,
# at, and two above capacity — enough points to localize the knee
RATE_FRACS = (0.25, 0.5, 1.0, 2.0, 4.0)

SMOKE_PARAMS = dict(n_requests=10, slots=2, ctx=64, prompt_len=(4, 12),
                    out_len=(2, 12), budget=8, slo_ttft=40.0,
                    preempt_age=40.0, rate_fracs=(0.5, 1.0, 2.5),
                    record="traffic_bench_smoke")

# shared-prefix mode (run_shared_prefix): Zipf draws over a small
# system-prompt pool, cache-on vs cache-off on the same traffic.
# prefix_len is a multiple of prefill_bucket_min (8) so the shared part
# is a cacheable chunk boundary; rate_frac 1.5x capacity queues enough
# that the saved prefill dispatches show up in TTFT, not just counters.
SHARED_SMOKE_PARAMS = dict(n_requests=10, slots=2, ctx=64, n_prefixes=3,
                           prefix_len=16, zipf_s=1.1, user_len=(3, 10),
                           out_len=(2, 8), budget=8, slo_ttft=40.0,
                           rate_frac=1.5, cache_bytes=1 << 24,
                           concurrency=4, record="prefix_bench_smoke")


def _capacity_est(slots, out_len) -> float:
    """Crude saturation-rate estimate (requests per dispatch-unit): each
    request holds a slot for about its decode-token count of dispatch
    units plus ~2 prefill chunks, and ``slots`` lanes share every decode
    dispatch."""
    mean_out = (out_len[0] + out_len[1]) / 2.0
    return slots / (mean_out + 2.0)


def _warm(arch, params, slots, ctx, prompt_len, budget):
    """Populate the shared per-arch executable caches for every bucket
    the sweep can touch (budget-truncated chunks pad to powers of two up
    to the longest prompt's bucket), so measured latency is the serving
    steady state, not compile time."""
    cfg = ServeConfig(batch_slots=slots, max_ctx=ctx)
    eng = Engine(arch, params, cfg)
    # every power-of-two bucket up to the longest prompt's: the static
    # baseline dispatches whole prompts (any bucket in that range), the
    # budgeted scheduler only chunks <= budget, but both share the caches
    lens, b = set(), cfg.prefill_bucket_min
    while True:
        lens.add(min(b, ctx - 2))
        if b >= prompt_len[1]:
            break
        b *= 2
    for n in sorted(lens):
        eng.add_request([1] * n)
        eng.step()
        for s in range(slots):
            eng.release_slot(s)


def bench_arch(name, *, n_requests, slots, ctx, prompt_len, out_len,
               budget, slo_ttft, preempt_age, rate_fracs, seed=0):
    arch = get_config(name).reduced()
    params = init_params(jax.random.PRNGKey(0), arch)
    _warm(arch, params, slots, ctx, prompt_len, budget)
    cap = _capacity_est(slots, out_len)

    res = {"capacity_est_req_per_step": cap, "rates": {}}
    sweep = []   # (frac, offered_tok_per_step, sched_goodput)
    for frac in rate_fracs:
        rate = frac * cap
        traffic = synth_traffic(n_requests, rate, seed=seed,
                                vocab_size=arch.vocab_size,
                                prompt_len=prompt_len, out_len=out_len)
        total_tokens = sum(t.max_new_tokens for t in traffic)
        offered = rate * total_tokens / n_requests
        cell = {"rate_req_per_step": rate,
                "offered_tok_per_step": offered}
        for mode, make in (
                # preempt_age at the TTFT SLO: a queue-head request aging
                # past it evicts the youngest running request (LIFO
                # victim), so preemption shows up in the goodput curves
                # above capacity — the ``preempted`` count is exact-gated
                # like the rest of the scheduling counters. Tighter ages
                # thrash under sustained overload (victim recompute beats
                # the rescued request's odds of still making its SLO) and
                # hand the goodput win back to static batching
                ("scheduler", lambda e, c: Scheduler(
                    e, SchedulerConfig(prefill_token_budget=budget,
                                       preempt_age=preempt_age),
                    clock=c.now)),
                ("static", lambda e, c: StaticBatchScheduler(
                    e, clock=c.now))):
            clock = StepClock()
            eng = Engine(arch, params,
                         ServeConfig(batch_slots=slots, max_ctx=ctx))
            sched = make(eng, clock)
            t0 = time.perf_counter()
            run_open_loop(sched, traffic, tick=clock.tick)
            wall = time.perf_counter() - t0
            m = sched.metrics(slo_ttft=slo_ttft)
            m.pop("pj_per_token"), m.pop("energy_pj")  # CIM off: priced below
            m["run_wall_s"] = wall
            cell[mode] = m
            emit(f"traffic/{name}/{frac}x/{mode}", wall * 1e6,
                 f"goodput_step={m['goodput_tok_per_step']:.3f}"
                 f";in_slo={m['completed_in_slo']}/{m['completed']}")
        cell["goodput_ratio_vs_static"] = (
            cell["scheduler"]["goodput_tok_per_step"]
            / max(cell["static"]["goodput_tok_per_step"], 1e-12))
        res["rates"][f"{frac}x"] = cell
        sweep.append((frac, offered,
                      cell["scheduler"]["goodput_tok_per_step"]))

    # saturation knee: first rate whose marginal goodput per marginal
    # offered load drops below 0.5 — service saturated, queueing onward
    knee = None
    for (f0, o0, g0), (f1, o1, g1) in zip(sweep, sweep[1:]):
        if (g1 - g0) / max(o1 - o0, 1e-12) < 0.5:
            knee = f1
            break
    res["knee_rate_frac"] = knee
    res["beats_static_above_capacity"] = all(
        c["goodput_ratio_vs_static"] > 1.0
        for label, c in res["rates"].items()
        if float(label[:-1]) > 1.0)

    # deployment energy next to the traffic curves (ledger-derived, as in
    # serve_bench: the engines above serve digital, the CIM path is priced
    # on the shape-only trace)
    cim_arch = arch if arch.cim.enabled else arch.replace(
        cim=arch.cim.with_mode("grmac"))
    res["pj_per_token"] = costs.price_ledger(
        costs.trace_decode(cim_arch), 1, n_cols=1 << 8)["pj_per_token"]
    return res


def run(n_requests=32, slots=4, ctx=256, prompt_len=(8, 48),
        out_len=(4, 32), budget=16, slo_ttft=80.0, preempt_age=80.0,
        rate_fracs=RATE_FRACS, archs=None, record="traffic_bench", seed=0):
    from repro.analysis.invariants import run_scheduler_invariants

    out = {
        "params": {"n_requests": n_requests, "slots": slots, "ctx": ctx,
                   "prompt_len": list(prompt_len),
                   "out_len": list(out_len), "budget": budget,
                   "slo_ttft_steps": slo_ttft,
                   "preempt_age_steps": preempt_age,
                   "rate_fracs": list(rate_fracs), "seed": seed},
        "archs": {},
    }
    for label, name in (archs or ARCHS):
        out["archs"][label] = {
            "config": name,
            **bench_arch(name, n_requests=n_requests, slots=slots, ctx=ctx,
                         prompt_len=prompt_len, out_len=out_len,
                         budget=budget, slo_ttft=slo_ttft,
                         preempt_age=preempt_age, rate_fracs=rate_fracs,
                         seed=seed)}
    # the compile-budget / one-transfer invariants, proven under the
    # instrumented scheduler, in the same record the latency comes from
    out["invariants"] = run_scheduler_invariants(("qwen2-1.5b",))

    print(f"\n{'arch':<6} {'rate':>6} {'offered':>8} "
          f"{'goodput sched':>14} {'goodput static':>15} {'ratio':>6} "
          f"{'in-SLO':>7} {'ttft p99 ms':>12} {'qmax':>5}")
    for label, a in out["archs"].items():
        for rl, c in a["rates"].items():
            s, st = c["scheduler"], c["static"]
            print(f"{label:<6} {rl:>6} {c['offered_tok_per_step']:>8.2f} "
                  f"{s['goodput_tok_per_step']:>14.3f} "
                  f"{st['goodput_tok_per_step']:>15.3f} "
                  f"{c['goodput_ratio_vs_static']:>6.2f} "
                  f"{s['completed_in_slo']:>3}/{s['completed']:<3} "
                  f"{s['ttft_p99_ms']:>12.1f} {s['queue_depth_max']:>5}")
        print(f"{label:<6} knee at {a['knee_rate_frac']}x capacity; "
              f"beats static above capacity: "
              f"{a['beats_static_above_capacity']}; "
              f"{a['pj_per_token']:.1f} pJ/token (CIM decode)")
    save_json(record, out)
    return out


def _sched_run(arch, params, traffic, *, slots, ctx, budget, slo_ttft,
               cache_bytes=None, admission="fifo", age_bound=None,
               closed_concurrency=None):
    """One scheduler run over ``traffic`` on a fresh engine + StepClock;
    returns (metrics, {rid: generated tokens}). ``closed_concurrency``
    switches from the open-loop Poisson driver to the fixed-concurrency
    closed-loop one."""
    clock = StepClock()
    eng = Engine(arch, params,
                 ServeConfig(batch_slots=slots, max_ctx=ctx,
                             prefix_cache_bytes=cache_bytes))
    sched = Scheduler(eng, SchedulerConfig(prefill_token_budget=budget,
                                           admission=admission,
                                           admission_age_bound=age_bound),
                      clock=clock.now)
    t0 = time.perf_counter()
    if closed_concurrency is None:
        run_open_loop(sched, traffic, tick=clock.tick)
    else:
        run_closed_loop(sched, traffic, concurrency=closed_concurrency,
                        tick=clock.tick)
    wall = time.perf_counter() - t0
    m = sched.metrics(slo_ttft=slo_ttft)
    m.pop("pj_per_token"), m.pop("energy_pj")  # CIM off: priced separately
    m["run_wall_s"] = wall
    outs = {r.rid: list(r.generated) for r in sched.finished}
    return m, outs


def bench_shared_prefix_arch(name, *, n_requests, slots, ctx, n_prefixes,
                             prefix_len, zipf_s, user_len, out_len, budget,
                             slo_ttft, rate_frac, cache_bytes, concurrency,
                             seed=0):
    arch = get_config(name).reduced()
    params = init_params(jax.random.PRNGKey(0), arch)
    plen = (prefix_len + user_len[0], prefix_len + user_len[1])
    _warm(arch, params, slots, ctx, plen, budget)
    cap = _capacity_est(slots, out_len)
    rate = rate_frac * cap
    traffic = synth_shared_prefix_traffic(
        n_requests, rate, seed=seed, vocab_size=arch.vocab_size,
        n_prefixes=n_prefixes, prefix_len=prefix_len, zipf_s=zipf_s,
        user_len=user_len, out_len=out_len)

    res = {"rate_req_per_step": rate, "modes": {}}
    common = dict(slots=slots, ctx=ctx, budget=budget, slo_ttft=slo_ttft)
    outs = {}
    for mode, cb in (("cache_off", None), ("cache_on", cache_bytes)):
        m, outs[mode] = _sched_run(arch, params, traffic,
                                   cache_bytes=cb, **common)
        res["modes"][mode] = m
        emit(f"prefix/{name}/{mode}", m["run_wall_s"] * 1e6,
             f"ttft_p50_steps={m['ttft_p50_steps']:.1f}"
             f";dispatches={m['prefill_dispatches']}"
             f";hits={m.get('prefix_hits', 0)}")

    # prefill energy at the CIM operating point, priced by prompt tokens
    # actually dispatched: hits convert straight into analog MAC + ADC
    # work not done. Per-prefill-token price at the budget-sized bucket
    # (the chunk the scheduler dispatches), normalized per output token.
    cim_arch = arch if arch.cim.enabled else arch.replace(
        cim=arch.cim.with_mode("grmac"))
    price = costs.price_ledger(
        costs.trace_prefill(cim_arch, bucket=budget), budget,
        n_cols=1 << 8)["pj_per_token"]
    for m in res["modes"].values():
        m["prefill_pj_per_output_token"] = (
            price * m["prefill_tokens_dispatched"]
            / max(m["generated_tokens"], 1))
    off, on = res["modes"]["cache_off"], res["modes"]["cache_on"]

    # the acceptance leaves, all deterministic under StepClock and
    # exact-gated by compare.py: the hit streams must be bit-identical
    # to cold prefill AND strictly cheaper to serve
    res["outputs_identical"] = outs["cache_off"] == outs["cache_on"]
    res["cache_wins_ttft"] = on["ttft_p50_steps"] < off["ttft_p50_steps"]
    res["cache_wins_dispatches"] = (on["prefill_dispatches"]
                                    < off["prefill_dispatches"])
    res["prefill_pj_reduced"] = (on["prefill_pj_per_output_token"]
                                 < off["prefill_pj_per_output_token"])
    res["prefill_pj_reduction_pct"] = 100.0 * (
        1.0 - on["prefill_pj_per_output_token"]
        / max(off["prefill_pj_per_output_token"], 1e-12))

    # satellite cells on the same traffic, cache on: shortest-prompt
    # admission (anti-starvation bound at the SLO) and the closed-loop
    # fixed-concurrency client model — their scheduling counts ride the
    # same exact gates
    m, _ = _sched_run(arch, params, traffic, cache_bytes=cache_bytes,
                      admission="shortest_prompt", age_bound=slo_ttft,
                      **common)
    res["modes"]["shortest_prompt"] = m
    m, _ = _sched_run(arch, params, traffic, cache_bytes=cache_bytes,
                      closed_concurrency=concurrency, **common)
    m["concurrency"] = concurrency
    res["modes"]["closed_loop"] = m
    return res


def run_shared_prefix(n_requests=32, slots=4, ctx=256, n_prefixes=4,
                      prefix_len=32, zipf_s=1.1, user_len=(4, 24),
                      out_len=(4, 16), budget=8, slo_ttft=80.0,
                      rate_frac=1.5, cache_bytes=1 << 26, concurrency=8,
                      archs=None, record="prefix_bench", seed=0):
    """Shared-prefix traffic sweep: cache-on vs cache-off on identical
    seeded Zipf system-prompt traffic, per arch family, plus the
    shortest-prompt-admission and closed-loop satellite cells. See the
    module docstring's determinism contract — every count and derived
    win/loss boolean here is exact-gated."""
    from repro.analysis.invariants import run_prefix_invariants

    out = {
        "params": {"n_requests": n_requests, "slots": slots, "ctx": ctx,
                   "n_prefixes": n_prefixes, "prefix_len": prefix_len,
                   "zipf_s": zipf_s, "user_len": list(user_len),
                   "out_len": list(out_len), "budget": budget,
                   "slo_ttft_steps": slo_ttft, "rate_frac": rate_frac,
                   "cache_bytes": cache_bytes, "concurrency": concurrency,
                   "seed": seed},
        "archs": {},
    }
    for label, name in (archs or ARCHS):
        out["archs"][label] = {
            "config": name,
            **bench_shared_prefix_arch(
                name, n_requests=n_requests, slots=slots, ctx=ctx,
                n_prefixes=n_prefixes, prefix_len=prefix_len,
                zipf_s=zipf_s, user_len=user_len, out_len=out_len,
                budget=budget, slo_ttft=slo_ttft, rate_frac=rate_frac,
                cache_bytes=cache_bytes, concurrency=concurrency,
                seed=seed)}
    # compile/transfer invariants re-proven under the hit-heavy trace,
    # in the same record the cache wins come from
    out["invariants"] = run_prefix_invariants(("qwen2-1.5b",))

    print(f"\n{'arch':<6} {'mode':<16} {'ttft p50':>9} {'dispatch':>9} "
          f"{'pfill tok':>10} {'saved':>6} {'hits':>5} {'pJ/out-tok':>11}")
    for label, a in out["archs"].items():
        for mode, m in a["modes"].items():
            print(f"{label:<6} {mode:<16} {m['ttft_p50_steps']:>9.1f} "
                  f"{m['prefill_dispatches']:>9} "
                  f"{m['prefill_tokens_dispatched']:>10} "
                  f"{m['prefill_tokens_saved']:>6} "
                  f"{m.get('prefix_hits', 0):>5} "
                  f"{m['prefill_pj_per_output_token'] if 'prefill_pj_per_output_token' in m else float('nan'):>11.1f}")
        print(f"{label:<6} outputs identical: {a['outputs_identical']}; "
              f"cache wins ttft/dispatches: {a['cache_wins_ttft']}/"
              f"{a['cache_wins_dispatches']}; prefill pJ -"
              f"{a['prefill_pj_reduction_pct']:.1f}%")
    save_json(record, out)
    return out


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--ctx", type=int, default=256)
    ap.add_argument("--budget", type=int, default=16,
                    help="prefill token budget per scheduler step")
    ap.add_argument("--slo-ttft", type=float, default=80.0,
                    help="TTFT SLO in virtual dispatch-units")
    ap.add_argument("--preempt-age", type=float, default=80.0,
                    help="queue-head age (virtual units) that triggers "
                         "LIFO preemption of a running request")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for the CI bench lane")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="run the shared-prefix cache-on/cache-off mode "
                         "(prefix_bench record) instead of the rate sweep")
    args = ap.parse_args()
    if args.shared_prefix:
        # separate records: smoke runs must not clobber the committed
        # full-size jsons
        run_shared_prefix(**SHARED_SMOKE_PARAMS) if args.smoke \
            else run_shared_prefix()
    elif args.smoke:
        run(**SMOKE_PARAMS)
    else:
        run(n_requests=args.requests, slots=args.slots, ctx=args.ctx,
            budget=args.budget, slo_ttft=args.slo_ttft,
            preempt_age=args.preempt_age)
