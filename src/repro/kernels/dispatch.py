"""Backend dispatch + shape-aware autotuning for the GR-MAC matmul.

One entry point, ``grmac_matmul(x, wq, ..., backend=...)``, selects among
the implementations and owns the shape-padding contract so every caller
(``ops.cim_matmul``, benchmarks, tests) sees plain ``(M, K) @ (K, N)``:

=================  ==========================================================
backend            implementation
=================  ==========================================================
``auto``           shape-aware plan (the default): ``pallas`` on TPU;
                   off-TPU the planner picks ``xla`` for small M (decode
                   shapes) and ``tiled`` for large M (training shapes),
                   either from the static heuristic or from a measured,
                   persisted autotune plan (see *Autotuning* below).
                   Overridable with ``REPRO_GRMAC_BACKEND``.
``xla``            ``xla.grmac_matmul_xla`` — fully-vectorized batched
                   einsum; fastest at small M, but materializes the full
                   ``(M, B, N)`` intermediates (bandwidth-bound at large M)
``tiled``          ``tiled.grmac_matmul_tiled`` — ``lax.scan`` over
                   M(xN)-tiles with the den/ADC/renorm epilogue fused in
                   the tile body; the large-M winner (>=2x over both
                   ``xla`` and ``ref`` at train_large_m on CPU)
``pallas``         ``grmac_matmul.grmac_matmul_pallas`` — the TPU kernel
                   (VMEM-streaming MXU lowering); it compiles for the TPU
                   only and fails to lower anywhere else
``pallas_interpret``  the Pallas kernel forced through the interpreter —
                   a *debug* backend for cross-checking the TPU lowering's
                   semantics off-TPU; orders of magnitude slower than
                   ``xla`` (see ``benchmarks/kernel_bench.py``)
``ref``            ``ref.grmac_matmul_ref`` — the readable pure-jnp oracle
=================  ==========================================================

Autotuning
----------
``plan_for`` maps ``(M, K, N, granularity, fmt_x, fmt_w, n_r, platform)``
to a ``Plan(backend, tile_m, tile_n)``:

1. an in-memory plan table (warm path: zero overhead after first use);
2. the persisted JSON plan cache (``REPRO_GRMAC_PLAN_CACHE``, default
   ``~/.cache/repro/grmac_plans.json``) — plans measured once are reused
   across processes, so serving/training never pay the probe twice. The
   file carries a schema ``version`` (``PLAN_CACHE_VERSION``); caches
   written under a different version — or pre-versioned flat files — are
   ignored with a warning and rewritten on the next persisted plan, so
   growing the candidate space (e.g. tile_n) can never silently
   serve stale measurements;
3. with ``REPRO_GRMAC_AUTOTUNE=1``: a micro-autotune that times each
   candidate ``(backend, tile_m, tile_n)`` on synthetic operands of the
   requested shape, persists the winner, and returns it (skipped inside
   jax traces — the heuristic answers there and the next eager call
   probes);
4. otherwise: the static heuristic — ``pallas`` on TPU, ``tiled`` when
   ``M >= 64`` (the measured CPU crossover), else ``xla``.

Padding: every backend requires ``K % n_r == 0`` (an analog column always
has ``n_r`` physical rows; zero-padded entries still contribute their
minimum-capacitance gain to the block denominator, exactly like unused
hardware rows). The Pallas backends additionally need M, N and K padded to
the blocks that ``pallas_blocks`` plans from the call's shape (M to 8 rows
up to a 128-row block, N to 128 columns, K to ``lcm(128, n_r)``).
``grmac_matmul`` pads with zeros and slices the result, so all families see
the *same* padded K blocks and agree numerically.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import time
import warnings
from typing import Dict, Optional

import jax

from repro.core.formats import FPFormat

from .grmac_matmul import grmac_matmul_pallas, named_kernel
from .ref import grmac_matmul_ref
from .tiled import default_tile_m, grmac_matmul_tiled, pad_to_multiple
from .xla import grmac_matmul_xla

__all__ = [
    "BACKENDS",
    "PLAN_CACHE_VERSION",
    "Plan",
    "pallas_blocks",
    "resolve_backend",
    "plan_for",
    "plan_cache_path",
    "clear_plan_cache",
    "grmac_matmul",
]

BACKENDS = ("auto", "xla", "tiled", "pallas", "pallas_interpret", "ref")

_ENV_VAR = "REPRO_GRMAC_BACKEND"
# Opt-in numerics sanitizer (repro.analysis.sanitize): instruments the
# xla/tiled/ref backends with in-graph NaN/Inf, pre-ADC overflow and
# gain-range-limit checks via jax.debug.callback. Read per call so tests
# can monkeypatch the environment; when unset the
# backends receive sanitize=False / tag="" and stage *zero* extra
# primitives (bit-identical outputs, same jit cache keys as before).
# The Pallas backends are not instrumented (checks cannot run inside a
# pallas_call); sanitize runs are expected on xla/tiled/ref.
_SAN_ENV = "REPRO_SANITIZE"
# Opt-in micro-autotune (measured-once-then-cached planning).
_AUTOTUNE_ENV = "REPRO_GRMAC_AUTOTUNE"
# Override for the persisted plan-cache location.
_PLAN_CACHE_ENV = "REPRO_GRMAC_PLAN_CACHE"
# Plan-cache schema version. Bump when the plan record or the candidate
# space changes meaning (e.g. tile_n semantics):
# a cache written by a different schema is ignored with a warning rather
# than silently serving plans measured under different rules, and the
# next persisted plan rewrites the file under the current version.
PLAN_CACHE_VERSION = 1

# Measured CPU crossover (benchmarks/kernel_bench.py): at M=16 the batched
# einsum wins; from M=64 the fused tiles win at every granularity.
_TILED_MIN_M = 64

# VMEM caps of the Pallas launch's blocks (``pallas_blocks``), from a
# block_n sweep on one TPU v5e (PERF.md): the double-buffered f32 weight
# tile (block_n <= 4096 at block_k 128; wider tiles ran no faster), and the
# (block_m, block_n) f32 temporaries of the kernel body (128 x 8192 ran out
# of the default scoped VMEM, 128 x 4096 fits).
_PALLAS_W_TILE_BYTES = 4 << 20
_PALLAS_ACC_ELEMS = 128 * 4096


@dataclasses.dataclass(frozen=True)
class Plan:
    """A dispatch decision: which backend runs a shape, with which tiles.

    ``tile_m``/``tile_n`` are only meaningful for ``tiled`` (0 means the
    backend default / no N-tiling) and for ``pallas``, where a nonzero
    value pins the block (rounded up to 8 rows and 128 columns) and 0
    leaves it to ``pallas_blocks``.
    """
    backend: str
    tile_m: int = 0
    tile_n: int = 0
    source: str = "heuristic"          # heuristic | cache | autotune | fixed


def resolve_backend(backend: Optional[str] = None) -> str:
    """Resolve a backend *name*: None -> "auto" -> ``REPRO_GRMAC_BACKEND``.

    Returns "auto" when nothing forces a concrete choice — the shape-aware
    ``plan_for`` then decides per call. Explicit names always win over the
    environment.
    """
    b = backend or "auto"
    if b == "auto":
        b = os.environ.get(_ENV_VAR, "auto") or "auto"
    if b not in BACKENDS:
        raise ValueError(
            f"unknown GR-MAC backend {b!r}; expected one of {BACKENDS}")
    return b


# --------------------------------------------------------------- plan cache
_MEM_PLANS: Dict[str, Plan] = {}
_DISK_PLANS: Optional[Dict[str, dict]] = None
_DISK_PLANS_PATH: Optional[str] = None


def plan_cache_path() -> str:
    return os.environ.get(_PLAN_CACHE_ENV) or os.path.join(
        os.path.expanduser("~"), ".cache", "repro", "grmac_plans.json")


def clear_plan_cache(memory_only: bool = True) -> None:
    """Drop in-memory plans (and force a re-read of the disk cache). With
    ``memory_only=False`` also deletes the persisted JSON file."""
    global _DISK_PLANS, _DISK_PLANS_PATH
    _MEM_PLANS.clear()
    _DISK_PLANS = None
    _DISK_PLANS_PATH = None
    if not memory_only:
        try:
            os.remove(plan_cache_path())
        except OSError:
            pass


def _plan_key(m, k, n, granularity, fmt_x, fmt_w, n_r) -> str:
    return (f"{m}x{k}x{n}|{granularity}|{fmt_x.name}x{fmt_w.name}"
            f"|nr{n_r}|{jax.default_backend()}")


def _load_disk_plans() -> Dict[str, dict]:
    global _DISK_PLANS, _DISK_PLANS_PATH
    path = plan_cache_path()
    if _DISK_PLANS is None or _DISK_PLANS_PATH != path:
        try:
            with open(path) as f:
                raw = json.load(f)
        except (OSError, ValueError):
            raw = None
        if raw is None:
            _DISK_PLANS = {}
        elif (not isinstance(raw, dict)
              or raw.get("version") != PLAN_CACHE_VERSION):
            # Version mismatch (including pre-versioned caches, which have
            # no "version" key): the plans may have been measured under
            # different schema rules, so ignore them — the next persisted
            # plan rewrites the file under the current version.
            warnings.warn(
                f"ignoring GR-MAC plan cache {path!r}: schema version "
                f"{raw.get('version') if isinstance(raw, dict) else '?'} "
                f"!= {PLAN_CACHE_VERSION} (stale cache; it will be "
                "rewritten on the next autotuned plan)")
            _DISK_PLANS = {}
        else:
            _DISK_PLANS = raw.get("plans", {})
        _DISK_PLANS_PATH = path
    return _DISK_PLANS


def _persist_plan(key: str, plan: Plan, warm_us: float) -> None:
    path = plan_cache_path()
    plans = dict(_load_disk_plans())
    plans[key] = {"backend": plan.backend, "tile_m": plan.tile_m,
                  "tile_n": plan.tile_n, "warm_us": warm_us}
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"version": PLAN_CACHE_VERSION, "plans": plans},
                      f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        return  # read-only filesystems just skip persistence
    global _DISK_PLANS, _DISK_PLANS_PATH
    _DISK_PLANS, _DISK_PLANS_PATH = plans, path


def _heuristic_plan(m, k, n, n_r) -> Plan:
    if jax.default_backend() == "tpu":
        return Plan("pallas", source="heuristic")
    if m >= _TILED_MIN_M:
        return Plan("tiled", tile_m=default_tile_m(k, n, n_r),
                    source="heuristic")
    return Plan("xla", source="heuristic")


def _autotune_candidates(m, k, n, n_r):
    cands = [Plan("xla", source="autotune")]
    tm0 = default_tile_m(k, n, n_r)
    for tm in sorted({max(8, tm0 // 2), tm0, min(256, tm0 * 2)}):
        cands.append(Plan("tiled", tile_m=tm, source="autotune"))
    if n >= 2048:
        cands.append(Plan("tiled", tile_m=tm0, tile_n=1024,
                          source="autotune"))
    return cands


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def pallas_blocks(m: int, n: int, n_r: int, tile_m: int = 0,
                  tile_n: int = 0) -> tuple[int, int, int]:
    """``(block_m, block_n, block_k)`` of the Pallas launch for an
    ``(m, K) @ (K, n)`` call.

    ``block_m`` covers M up to one 128-row block, rounded up to the f32
    sublane tile of 8: a decode call's handful of rows is not padded to
    128. ``block_n`` is the widest multiple of 128 that divides N rounded up
    to 128 (so no weight is padded beyond that) whose f32 weight tile,
    double-buffered, fits ``_PALLAS_W_TILE_BYTES`` and whose
    ``block_m × block_n`` f32 temporaries fit ``_PALLAS_ACC_ELEMS``.
    ``block_k = lcm(128, n_r)``: whole analog columns on 128-lane tiles.
    Explicit ``tile_m``/``tile_n`` pin the blocks (rounded up to 8 and 128).
    Block shapes change no output bit: each output element's arithmetic
    depends only on its row, its column and the K order.
    """
    bk = math.lcm(128, n_r)
    bm = _round_up(tile_m, 8) if tile_m else min(128, _round_up(m, 8))
    if tile_n:
        return bm, _round_up(tile_n, 128), bk
    cols = _round_up(n, 128) // 128
    cap = max(1, min(_PALLAS_W_TILE_BYTES // (2 * 4 * bk * 128),
                     _PALLAS_ACC_ELEMS // (bm * 128)))
    width = max(d for d in range(1, min(cols, cap) + 1) if cols % d == 0)
    return bm, 128 * width, bk


def _run_plan(x, wq, plan: Plan, kwargs, tag: str = "") -> jax.Array:
    b = plan.backend
    if b in ("pallas", "pallas_interpret"):
        m, n = x.shape[0], wq.shape[1]
        bm, bn, bk = pallas_blocks(m, n, kwargs["n_r"], plan.tile_m,
                                   plan.tile_n)
        xp = pad_to_multiple(pad_to_multiple(x, 0, bm), 1, bk)
        wp = pad_to_multiple(pad_to_multiple(wq, 0, bk), 1, bn)
        # a named call site launches under its own name (``%grmac_<site>``
        # in the device trace); only the launch, not the padding around it
        kernel = named_kernel(f"grmac_{tag}") if tag else grmac_matmul_pallas
        out = kernel(
            xp, wp, block_m=bm, block_n=bn, block_k=bk,
            interpret=(b == "pallas_interpret"), **kwargs)
        return out[:m, :n]

    san = os.environ.get(_SAN_ENV, "0") not in ("", "0")
    # tag="" when the sanitizer is off: the site label is only consumed by
    # sanitize reports, and keeping it constant avoids one jit cache entry
    # per call site in the normal (uninstrumented) regime.
    san_kw = dict(sanitize=san, tag=(tag if san else ""))
    xp = pad_to_multiple(x, 1, kwargs["n_r"])
    wp = pad_to_multiple(wq, 0, kwargs["n_r"])
    if b == "tiled":
        return grmac_matmul_tiled(xp, wp, tile_m=plan.tile_m,
                                  tile_n=plan.tile_n, **san_kw, **kwargs)
    if b == "xla":
        return grmac_matmul_xla(xp, wp, **san_kw, **kwargs)
    return grmac_matmul_ref(xp, wp, **san_kw, **kwargs)


def _probe(key, m, k, n, granularity, fmt_x, fmt_w, n_r, enob) -> Plan:
    """Measure the candidate plans once on synthetic operands and persist
    the winner. Data-independent: only shapes matter, so the probe never
    needs (or touches) the caller's arrays."""
    from repro.core.formats import quantize  # local: avoid cycle at import

    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.uniform(kx, (m, k), minval=-1.0, maxval=1.0)
    wq = quantize(jax.random.uniform(kw, (k, n), minval=-1.0, maxval=1.0),
                  fmt_w)
    kwargs = dict(fmt_x=fmt_x, fmt_w=fmt_w, n_r=n_r, enob=enob,
                  granularity=granularity)
    best, best_us = None, float("inf")
    for cand in _autotune_candidates(m, k, n, n_r):
        try:
            jax.block_until_ready(_run_plan(x, wq, cand, kwargs))  # compile
            times = []
            for _ in range(2):
                t0 = time.perf_counter()
                jax.block_until_ready(_run_plan(x, wq, cand, kwargs))
                times.append(time.perf_counter() - t0)
            us = min(times) * 1e6
        except Exception:
            continue
        if us < best_us:
            best, best_us = cand, us
    if best is None:
        return _heuristic_plan(m, k, n, n_r)
    _persist_plan(key, best, best_us)
    return best


def _tracing() -> bool:
    """True inside a jax trace, where probing would stage timing runs into
    the caller's graph (the heuristic/cache answer there instead)."""
    from jax._src import core  # trace_state_clean is not public API
    return not core.trace_state_clean()


def plan_for(
    m: int,
    k: int,
    n: int,
    *,
    granularity: str = "row",
    fmt_x: FPFormat,
    fmt_w: FPFormat,
    n_r: int = 32,
    enob: float = 8.0,
    backend: Optional[str] = None,
) -> Plan:
    """Shape-aware dispatch plan (see module docstring for the lookup
    order). Explicit backend names short-circuit to a fixed plan."""
    b = resolve_backend(backend)
    if b != "auto":
        return Plan(b, source="fixed")
    key = _plan_key(m, k, n, granularity, fmt_x, fmt_w, n_r)
    hit = _MEM_PLANS.get(key)
    if hit is not None:
        return hit
    rec = _load_disk_plans().get(key)
    # "auto" is a planner input, never a valid planned backend: a corrupt
    # or version-skewed cache entry must not fall through to the oracle
    if (rec is not None and rec.get("backend") in BACKENDS
            and rec["backend"] != "auto"):
        plan = Plan(rec["backend"], int(rec.get("tile_m", 0)),
                    int(rec.get("tile_n", 0)), source="cache")
        _MEM_PLANS[key] = plan
        return plan
    if (os.environ.get(_AUTOTUNE_ENV, "0") not in ("", "0")
            and not _tracing()):
        plan = _probe(key, m, k, n, granularity, fmt_x, fmt_w, n_r, enob)
        _MEM_PLANS[key] = plan
        return plan
    # heuristic answers are NOT memoized into _MEM_PLANS: a later call with
    # autotune enabled (or a freshly persisted plan) must still win.
    return _heuristic_plan(m, k, n, n_r)


def grmac_matmul(
    x: jax.Array,
    wq: jax.Array,
    *,
    fmt_x: FPFormat,
    fmt_w: FPFormat,
    n_r: int = 32,
    enob: float = 8.0,
    granularity: str = "row",
    backend: Optional[str] = None,
    tile_m: Optional[int] = None,
    tile_n: Optional[int] = None,
    tag: str = "",
) -> jax.Array:
    """(M, K) @ (K, N) GR-MAC matmul via the planned backend.

    ``x`` pre-scaled to [-1, 1]; ``wq`` already on the weight format grid.
    Arbitrary M/N/K (padding handled here); float32 output. ``tile_m`` /
    ``tile_n`` override the plan's tile sizes (``tiled``/``pallas`` only).
    ``tag`` names the call site: in ``REPRO_SANITIZE=1`` violation reports,
    and as the Pallas launch's name (``grmac_<tag>``) in TPU traces
    (metadata only; never changes numerics or planning).
    """
    m, k = x.shape
    n = wq.shape[1]
    plan = plan_for(m, k, n, granularity=granularity, fmt_x=fmt_x,
                    fmt_w=fmt_w, n_r=n_r, enob=enob, backend=backend)
    if tile_m is not None or tile_n is not None:
        plan = dataclasses.replace(
            plan,
            tile_m=plan.tile_m if tile_m is None else tile_m,
            tile_n=plan.tile_n if tile_n is None else tile_n)
    kwargs = dict(fmt_x=fmt_x, fmt_w=fmt_w, n_r=n_r, enob=enob,
                  granularity=granularity)
    return _run_plan(x, wq, plan, kwargs, tag=tag)
