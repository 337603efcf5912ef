"""Speculative-decode benchmark: accepted-tokens/step, TTLT speedup, and
the pJ/accepted-token energy verdict per arch family.

For each cache family the engine serves (attn / rglru / ssm / moe), a
single request decodes ``--tokens`` tokens sequentially and under
``serving.speculative.SpecDecoder``:

* ``selfdraft``  — the target config drafts for itself. Greedy
  acceptance is structurally total, so every counter
  (``accepted_tokens_per_step``, draft/verify/repair dispatch counts)
  and the bit-exactness boolean are pure functions of the config —
  benchmarks/compare.py gates them exactly.
* ``quantdraft`` — a fakequant-numerics drafter of the same weights: a
  genuinely different (cheaper) numerics path whose mispredictions
  exercise rollback + repair. Its acceptance rate depends on platform
  numerics, so only ``outputs_identical`` (the greedy exactness
  guarantee, which holds for ANY drafter) is exact-gated; the rate is
  reported.
* ``ttlt_ms``    — wall time from ``add_request`` to the last of
  ``--tokens`` tokens, sequential vs speculative (ratio-gated like the
  other wall-clock leaves; the speedup is the headline).
* ``energy``     — the analytic pJ/accepted-token account
  (``speculative.price_speculation``) of the *measured* selfdraft
  dispatch counters re-priced on the ``grmac`` CIM deployment of the
  same arch, digital drafter: sequential analog decode vs digital
  draft + analog chunk verify. Deterministic (seeded-MC ENOB pricing),
  so the boolean verdict is exact-gated. On today's constants the
  verdict is honest and negative — a digital drafter's conventional
  fJ/op dwarfs the GR-MAC path it saves, so speculation is a latency
  win that *costs* energy unless the drafter itself is an aggressive
  low-energy analog config (the ``site_overrides`` draft policy).

Run:  PYTHONPATH=src python -m benchmarks.spec_bench [--smoke]
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.models import init_params
from repro.serving.engine import Engine, ServeConfig
from repro.serving.params import SamplingParams
from repro.serving.speculative import (SpecConfig, SpecDecoder,
                                       draft_arch_for, price_speculation)
from benchmarks.common import emit, save_json
from repro.compile_cache import enable_compile_cache

ARCHS = [
    ("attn", "qwen2-1.5b"),
    ("rglru", "recurrentgemma-9b"),
    ("ssm", "mamba2-1.3b"),
    ("moe", "grok-1-314b"),
]
# shared by the --smoke CLI (refreshing the committed record) and
# benchmarks/compare.py's fresh run: the gate compares like for like
SMOKE_PARAMS = dict(prompt_len=8, tokens=16, k=4, slots=2, ctx=64,
                    record="spec_bench_smoke")


def _decode_all(eng, stepper, slot, prompt_len, tokens, max_steps=4096):
    for _ in range(max_steps):
        if not eng.active[slot] or \
                len(eng.tokens[slot]) - prompt_len >= tokens:
            break
        stepper()
    return eng.tokens[slot][prompt_len:][:tokens]


def bench_arch(name, prompt_len, tokens, k, slots, ctx, trials=3):
    arch = get_config(name).reduced()
    params = init_params(jax.random.PRNGKey(0), arch)
    prompt = [int(t) for t in
              np.random.RandomState(0).randint(1, arch.vocab_size,
                                               prompt_len)]
    cfg = ServeConfig(batch_slots=slots, max_ctx=ctx)
    sp = SamplingParams(max_tokens=tokens)

    def run(spec_draft, timed=False):
        best = float("inf")
        for _ in range(trials if timed else 1):
            eng = Engine(arch, params, cfg)
            dec = (SpecDecoder(eng, SpecConfig(k=k, draft=spec_draft))
                   if spec_draft is not None else None)
            step = (lambda: dec.step()) if dec else (lambda: eng.step())
            t0 = time.perf_counter()
            slot = eng.add_request(prompt, params=sp)
            toks = _decode_all(eng, step, slot, prompt_len, tokens)
            best = min(best, time.perf_counter() - t0)
            if not timed:
                break
        return toks, eng, best

    run(None)            # warm shared executables (compile excluded)
    run("self")
    ref, _, seq_ms = run(None, timed=True)
    got, eng_s, spec_ms = run("self", timed=True)
    st = eng_s.stats
    res = {
        "selfdraft": {
            "outputs_identical": got == ref,
            "accepted_tokens_per_step": st["spec_tokens"]
            / max(1, st["spec_steps"]),
            "spec_steps": st["spec_steps"],
            "spec_tokens": st["spec_tokens"],
            "draft_dispatches": st["draft_dispatches"],
            "verify_dispatches": st["verify_dispatches"],
            "repair_dispatches": st["repair_dispatches"],
        },
        "seq": {"ttlt_ms": seq_ms * 1e3},
        "spec": {"ttlt_ms": spec_ms * 1e3},
        "ttlt_speedup": seq_ms / spec_ms,
    }
    # the different-numerics drafter: exactness is guaranteed, the
    # acceptance rate is a measurement (platform-dependent numerics)
    qarch = arch.replace(cim=arch.cim.with_mode("fakequant"))
    gq, eng_q, _ = run(qarch)
    res["quantdraft"] = {
        "outputs_identical": gq == ref,
        "accepted_rate": eng_q.stats["spec_tokens"]
        / max(1, eng_q.stats["spec_steps"]) / k,
        "repair_dispatches_seen": eng_q.stats["repair_dispatches"],
    }
    # analytic energy verdict on the grmac deployment of this arch, from
    # the measured (deterministic) selfdraft counters
    cim = arch if arch.cim.enabled else arch.replace(
        cim=arch.cim.with_mode("grmac"))
    bucket = max(cfg.prefill_bucket_min, 1 << max(0, k - 1).bit_length())
    res["energy"] = price_speculation(
        cim, draft_arch_for(cim, "digital"), res["selfdraft"], bucket,
        n_cols=1 << 8)
    emit(f"spec/{name}", spec_ms * 1e6,
         f"accept={res['selfdraft']['accepted_tokens_per_step']:.2f}"
         f";speedup={res['ttlt_speedup']:.2f}"
         f";identical={int(res['selfdraft']['outputs_identical'])}")
    return res


def run(prompt_len=64, tokens=64, k=4, slots=4, ctx=256, archs=None,
        record="spec_bench"):
    out = {
        "params": {"prompt_len": prompt_len, "tokens": tokens, "k": k,
                   "slots": slots, "ctx": ctx},
        "archs": {},
    }
    for label, name in (archs or ARCHS):
        out["archs"][label] = {"config": name,
                               **bench_arch(name, prompt_len, tokens, k,
                                            slots, ctx)}
    ups = [a["ttlt_speedup"] for a in out["archs"].values()]
    out["ttlt_speedup_geomean"] = float(np.exp(np.mean(np.log(ups))))

    print(f"\n{'arch':<8} {'acc/step':>9} {'identical':>10} "
          f"{'ttlt seq(ms)':>13} {'ttlt spec(ms)':>14} {'speedup':>8} "
          f"{'spec pJ/tok':>12} {'seq pJ/tok':>11} {'e-win':>6}")
    for label, a in out["archs"].items():
        e = a["energy"]
        print(f"{label:<8} "
              f"{a['selfdraft']['accepted_tokens_per_step']:>9.2f} "
              f"{str(a['selfdraft']['outputs_identical']):>10} "
              f"{a['seq']['ttlt_ms']:>13.1f} {a['spec']['ttlt_ms']:>14.1f} "
              f"{a['ttlt_speedup']:>7.2f}x "
              f"{e['spec_pj_per_accepted_token']:>12.1f} "
              f"{e['seq_pj_per_token']:>11.1f} "
              f"{str(bool(e['energy_win'])):>6}")
    print(f"geomean TTLT speedup (spec vs sequential): "
          f"{out['ttlt_speedup_geomean']:.2f}x")
    save_json(record, out)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes; refreshes the committed "
                         "spec_bench_smoke.json the CI gate compares")
    ap.add_argument("--tokens", type=int, default=64)
    ap.add_argument("--k", type=int, default=4)
    args = ap.parse_args()
    if args.smoke:
        run(**SMOKE_PARAMS)
    else:
        run(tokens=args.tokens, k=args.k)


if __name__ == "__main__":
    enable_compile_cache()
    main()
