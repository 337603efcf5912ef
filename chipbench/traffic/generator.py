"""The one traffic generator: reads a mix file (``traffic/<mix>.json``) and
makes the requests of a run from ``--seed``.

Adapted from ``repro.serving.scheduler.synth_traffic`` (seeded Poisson
arrivals, uniform token ids), with heavy-tailed lengths. Every seed gets
the same work: lengths and gaps are the midpoint quantiles of their
distributions, ``block`` of each, and each block of ``block`` requests
takes every quantile once, in a fixed order. The seed draws the token
ids (and, in the harness, the weights).

Mix keys:
    loop          "open": arrivals at fixed times (the only kind so far)
    rate_per_s    mean arrival rate; gaps exponential
    block         quantiles per distribution (requests per block)
    prompt/output {"median", "sigma", "lo", "hi"}: lognormal token counts
                  clipped to [lo, hi]
    requests      how many to make (at least what a window can consume)
"""
from __future__ import annotations

import dataclasses
import json
import statistics
from pathlib import Path
from typing import List

import numpy as np

MIX_DIR = Path(__file__).resolve().parent
# The order of sizes and gaps is one fixed permutation per block, the same
# for every seed: with the order drawn from the seed, the chat cell's p90
# time to first token spread by 31% between seeds on the chip while two
# runs of one seed agreed to a few percent (PERF.md).
SCHEDULE_SEED = 0


@dataclasses.dataclass
class Req:
    due_s: float                  # offset from the window's start
    prompt: List[int]
    max_tokens: int


def load_mix(name: str, base: Path = MIX_DIR) -> dict:
    path = base / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def lognormal_quantiles(dist: dict, k: int) -> np.ndarray:
    """The k midpoint quantiles of a clipped lognormal, as whole tokens."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / k) for i in range(k)])
    vals = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(vals), dist["lo"], dist["hi"]).astype(int)


def exponential_quantiles(k: int) -> np.ndarray:
    """Midpoint quantiles of Exp(1), rescaled to mean exactly 1."""
    q = -np.log1p(-(np.arange(k) + 0.5) / k)
    return q / q.mean()


def make_requests(mix: dict, seed: int, vocab_size: int) -> List[Req]:
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(SCHEDULE_SEED)
    k, n = int(mix["block"]), int(mix["requests"])
    plens = lognormal_quantiles(mix["prompt"], k)
    olens = lognormal_quantiles(mix["output"], k)
    gaps = exponential_quantiles(k)
    n_blocks = -(-n // k)

    def blockwise(vals):
        return np.concatenate([vals[order.permutation(k)]
                               for _ in range(n_blocks)])[:n]

    if mix["loop"] != "open":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    p_seq, o_seq, g_seq = blockwise(plens), blockwise(olens), blockwise(gaps)
    due = np.cumsum(g_seq) / float(mix["rate_per_s"])
    due = due - due[0]
    return [Req(due_s=float(due[i]),
                prompt=rng.integers(0, vocab_size, int(p_seq[i])).tolist(),
                max_tokens=int(o_seq[i]))
            for i in range(n)]
