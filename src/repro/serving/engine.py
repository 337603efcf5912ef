"""Batched serving engine: slot-based continuous batching over a fixed-size
decode batch with chunked, length-bucketed prefill and on-device sampling.

Prefill (``add_request``) pads each prompt chunk to a power-of-two bucket
and runs it through ``models.prefill_step`` — one compiled dispatch per
bucket (so O(ceil(len/bucket_max)) dispatches per prompt, vs one per token
in the legacy ``prefill_mode="token"`` path), with the compile cache
bounded by the O(log max_ctx) distinct bucket lengths per arch. Lanes not
being prefilled are frozen inside the dispatch (length 0), so no host-side
cache merging happens on the prefill path at all.

The **first output token is sampled from the prefill itself**: both
prefill modes adopt the last-valid-token logits (``prefill_step`` returns
them; the legacy token path's final dispatch produces the same ids), so
the first decode step feeds the first *generated* token — the seed-era
re-feed of the last prompt token, which wrote its K/V at positions len-1
AND len, is gone. ``add_request`` therefore appends one generated token
before returning (pass ``key`` to sample it when ``temperature > 0``).

Decode (``step``) is a single jit'd function over the whole batch that also
performs the per-lane cache merge *and* token selection (greedy argmax or
temperature-categorical) on device: logits never leave the device — the
host sees exactly one device→host transfer of a ``(batch_slots,)`` int32
array of sampled ids per step.

Per-token CIM energy accounting: ``energy_report`` prices the
``core.costs.CostLedger`` built by a shape-only trace of the *real* model
functions (prefill / decode / train phases) at each site's resolved design
— no hand-derived MAC census — and ``Engine.step`` /
``Engine.energy_per_token`` surface decode-phase pJ per generated token
next to the serving stats. The underlying required-ENOB Monte-Carlo is
memoized per design point (see ``core.costs.design_energy_fj``).

Machine-checked invariants
--------------------------
Two hot-path properties are enforced by ``repro.analysis.invariants``
(CI audit lane + tests/test_serving_invariants.py), not just documented:

1. **Compile budget**: at most one compilation per (arch, sampling mode)
   decode executable and per (arch, bucket) prefill executable, shared by
   every Engine via the module-level ``_decode_fn``/``_prefill_fn`` lru
   caches. A second trace of the same key means a retracing regression
   (the PR-1 recompile bug).
2. **One transfer per decode step**: every device→host crossing routes
   through ``Engine._fetch`` — one ``(batch_slots,)`` int32 array per
   ``step`` (and per prefill first-token selection). Adding a second
   transfer to the hot path fails the harness.

The seams the harness instruments are ``_decode_raw``/``_prefill_raw``
(the unjitted step bodies), ``_compiled_decode``/``_compiled_prefill``
(the per-engine dispatch points), and ``_fetch``; keep new hot-path code
flowing through them.

Incremental prefill (the scheduler seam)
----------------------------------------
``add_request`` runs a prompt's whole prefill in one blocking burst. The
continuous-batching scheduler (``repro.serving.scheduler``) instead needs
to drain prefills *chunk by chunk between decode steps*, so the engine
exposes the burst's three phases as first-class methods:

* ``begin_request(prompt)``  — claim + validate a slot (the lane is
  reserved but NOT in the decode batch yet);
* ``advance_prefill(slot, max_tokens)`` — one bucketed chunk dispatch of
  at most ``max_tokens`` prompt tokens (same power-of-two bucket
  executables as ``add_request``: no new compiles);
* ``finish_prefill(slot, key)`` — select the first output token from the
  last chunk's logits and activate the lane for decode.

``add_request`` is now literally ``begin → advance-until-drained →
finish``, so both entry points share one code path and stay equivalent.
``release_slot`` frees a lane mid-flight (scheduler-side stops at
``max_new_tokens``, preemption); ``free_slots`` is the admission-control
counter (active and mid-prefill lanes both count as occupied).

Prefix cache (``repro.serving.prefix_cache``): with
``ServeConfig.prefix_cache_bytes`` set (or an explicit ``PrefixCache``
passed to the constructor), ``begin_request`` adopts the longest cached
prefix into the claimed lane device-side and queues only the suffix, and
``advance_prefill`` stores new chunk-aligned boundary snapshots — both
composing with the existing bucket executables (no new compiles) and
the one-transfer invariant (nothing crosses to the host). The module
docstring of ``prefix_cache`` carries the full design note.

Sampling contract: ``temperature > 0`` samples **only when a PRNG key is
passed** — ``add_request``/``finish_prefill`` with ``temperature > 0``
and no ``key`` fall back to greedy argmax *with an explicit
``UserWarning``* (``step`` applies the same key-gated rule silently,
since it is called once per token; pass ``key=`` everywhere to sample).
Exception: a request with ``SamplingParams.seed`` set derives its lane
keys from its own seed (``fold_in(PRNGKey(seed), event_counter)`` inside
the executable) and therefore samples even without a per-step key — and
reproducibly, independent of slot placement and co-batched traffic.

Request API (``repro.serving.params``): per-request knobs enter through
``SamplingParams`` — ``add_request(prompt, params=...)`` /
``begin_request(prompt, params=...)`` — covering temperature, seed,
eos_id, max_tokens (enforced here: the lane frees itself with finish
reason ``"length"``) and the speculative ``spec_k``. The legacy
``eos_id=`` kwarg is still accepted for one release under a
``DeprecationWarning`` and behaves bit-identically. ``StepResult`` still
quacks like the old slot->token dict, but carries a typed
``outputs`` list of per-request ``RequestOutput`` records
(tokens-this-step, finished, finish_reason, lazy pJ/token).

Speculative seams (``repro.serving.speculative`` is the orchestrator;
the design note lives there): ``spec_snapshot``/``spec_restore`` capture
and lane-mask-restore the rollback-sensitive cache subtrees (local-attn
rings + RG-LRU/SSM recurrent states — global-attn KV needs none, stale
rows stay causally masked until overwritten), ``draft_step`` is one
greedy decode dispatch of a cheap same-weights draft arch against the
shared cache, ``verify_chunk`` reuses the *existing* bucketed prefill
executables as the exact greedy verifier (``prefill_step`` returns
per-position argmax ids precisely for this — zero new compiles),
``verify_chunk_sampled`` runs the rejection-rule acceptance on device,
and ``repair_chunk`` re-feeds the accepted prefix after a restore
(no fetch — acceptance already knows the tokens).
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import costs
from repro.models import decode_step, forward, init_cache, prefill_step
from repro.serving.params import RequestOutput, SamplingParams
from repro.serving.prefix_cache import (
    PrefixCache,
    restore_slot,
    snapshot_slot,
)
from repro.serving.spans import span

__all__ = ["ServeConfig", "Engine", "StepResult", "SamplingParams",
           "RequestOutput", "energy_report"]


class StepResult(dict):
    """``Engine.step`` result: slot id -> last sampled token (dict, as
    before), plus ``finished`` — the slot ids freed this step (per-slot
    EOS, ``max_tokens``, or context exhaustion), in ascending slot order —
    ``outputs`` — a typed ``RequestOutput`` per live request, carrying
    *all* tokens emitted this step (speculative steps emit several) and
    the finish reason — and ``pj_per_token``, the decode-phase CIM energy
    per generated token (None when the arch serves without the CIM path).
    The energy is resolved lazily on first access (a thunk into
    ``Engine.energy_per_token``'s memo), so the decode hot path never
    pays the trace/ENOB solve for callers that don't read it. A finished
    slot is immediately claimable by ``add_request``."""

    def __init__(self, tokens: dict, finished: List[int],
                 energy_fn: Optional[callable] = None,
                 outputs: Optional[List[RequestOutput]] = None):
        super().__init__(tokens)
        self.finished = finished
        self.outputs: List[RequestOutput] = outputs if outputs is not None \
            else []
        self._energy_fn = energy_fn

    @property
    def pj_per_token(self) -> Optional[float]:
        return self._energy_fn() if self._energy_fn is not None else None


def _merge_cache(old, new, mask):
    """Per-lane cache merge: lanes where ``mask`` is True take the new
    cache. Attention caches are positionally overwritten anyway, but
    recurrent states (SSM/RG-LRU) mutate on every pass and MUST be frozen
    for lanes that did not really advance. Stacked super-block caches carry
    the batch on axis 1; tail caches on axis 0."""
    def mrg(axis):
        def f(o, n):
            shape = [1] * o.ndim
            shape[axis] = -1
            return jnp.where(jnp.reshape(mask, shape), n, o)
        return f

    out = {}
    if "superblocks" in old:
        out["superblocks"] = jax.tree.map(
            mrg(1), old["superblocks"], new["superblocks"])
    if "tail" in old:
        out["tail"] = jax.tree.map(mrg(0), old["tail"], new["tail"])
    return out


def _lane_keys(key, seeds, ctrs):
    """Per-lane sampling keys: unseeded lanes split the caller's per-step
    key (the legacy stream, bit-identical when no lane is seeded); a lane
    with ``seeds[i] >= 0`` instead derives ``fold_in(PRNGKey(seed), ctr)``
    from its own seed and per-lane sampling-event counter — a stream that
    is a pure function of (seed, event index), independent of slot
    placement, batch composition and the caller's key."""
    base = jax.random.split(key, seeds.shape[0])

    def pick(bk, seed, ctr):
        sk = jax.random.fold_in(jax.random.PRNGKey(jnp.maximum(seed, 0)),
                                ctr)
        return jnp.where(seed >= 0, sk, bk)

    return jax.vmap(pick)(base, seeds, ctrs)


def _decode_raw(arch: ArchConfig, sample: bool):
    """The unjitted fused decode-step body (forward + active-mask cache
    merge + token selection). Exposed separately from ``_decode_fn`` so the
    invariant harness (``repro.analysis.invariants``) can wrap it in a
    compile counter before jitting — same function, same trace.

    ``temp`` is a per-lane (B,) float32 vector (mixed greedy/sampled
    batches: a lane with ``temp <= 0`` takes the argmax even in the
    sampled executable); ``seeds``/``ctrs`` are the per-lane (B,) int32
    seed (-1 = unseeded) and sampling-event counter feeding
    ``_lane_keys``. With every lane unseeded and a uniform temperature
    this reproduces the legacy scalar-temperature stream bit-for-bit."""
    def fn(params, toks, cache, lengths, active, key, temp, seeds, ctrs):
        logits, new_cache = decode_step(params, toks, arch, cache, lengths)
        merged = _merge_cache(cache, new_cache, active)
        if sample:
            keys = _lane_keys(key, seeds, ctrs)
            nxt = jax.vmap(
                lambda k, lg, tt: jax.random.categorical(
                    k, lg / jnp.maximum(tt, 1e-6)))(keys, logits, temp)
            nxt = jnp.where(temp > 0, nxt, jnp.argmax(logits, axis=-1))
        else:
            nxt = jnp.argmax(logits, axis=-1)
        return nxt.astype(jnp.int32), merged

    # the program's name in traces and HLO: jit_decode_sample/_greedy
    fn.__name__ = "decode_sample" if sample else "decode_greedy"
    return fn


@functools.lru_cache(maxsize=64)
def _decode_fn(arch: ArchConfig, sample: bool):
    """One compiled decode executable per (arch, sampling mode), shared by
    every Engine.

    Sharing (rather than one ``jax.jit`` per Engine) keeps every engine for
    a given arch bitwise-consistent — XLA autotunes each compilation of the
    same HLO independently and a last-ulp logits difference flips greedy
    argmax near ties. The executable fuses the whole per-step hot path:
    decode forward, per-lane active-mask cache merge, and token selection
    (argmax, or per-lane temperature categorical when ``sample``), so
    logits and caches never cross the device boundary.
    """
    return jax.jit(_decode_raw(arch, sample))


def _prefill_raw(arch: ArchConfig, bucket: int):
    """The unjitted chunked-prefill body for one bucket length (see
    ``_decode_raw`` for why the raw/jit split exists). Shapes carry the
    bucket; it names the program (``jit_prefill_b<bucket>``)."""
    def fn(p, t, c, i, l):
        return prefill_step(p, t, arch, c, i, l)

    fn.__name__ = f"prefill_b{bucket}"
    return fn


@functools.lru_cache(maxsize=256)
def _prefill_fn(arch: ArchConfig, bucket: int):
    """One compiled chunked-prefill executable per (arch, bucket length),
    shared by every Engine. Buckets are powers of two (see
    ``Engine._bucket``), so the cache stays O(log max_ctx) per arch."""
    return jax.jit(_prefill_raw(arch, bucket))


def _verify_raw(arch: ArchConfig, bucket: int):
    """Sampled-acceptance speculative verify for one bucket length: a
    chunked prefill over ``[pending, d_1 .. d_{k-1}]`` plus the standard
    speculative rejection rule, entirely on device.

    Drafts here are *greedy* proposals (the draft model's argmax), i.e. a
    delta proposal distribution, so the textbook accept-with-prob
    ``min(1, p/q)`` reduces to: accept ``d_j`` with probability
    ``p(d_j)`` under the target distribution at that position; on the
    first rejection resample from ``p`` with the rejected token's mass
    zeroed out (the renormalized residual ``max(0, p - q)``), and when
    every draft survives sample the bonus token from the target's next
    distribution — unbiased w.r.t. sequential sampling (distribution-,
    not bit-, identical; see serving/speculative.py). Lanes with
    ``temp <= 0`` fall back to exact greedy acceptance, so mixed batches
    work. Returns a packed ``(B, S + 1)`` int32 array — emitted tokens
    (accepted drafts then the correction/bonus) followed by the per-lane
    emitted count — one fetch — plus the new cache.
    """
    def fn(params, toks, cache, index, lens, key, temp, seeds, ctrs):
        b, s = toks.shape
        idx = jnp.broadcast_to(jnp.asarray(index), (b,))
        lens_b = jnp.broadcast_to(jnp.asarray(lens), (b,))
        positions = idx[:, None] + jnp.arange(s)[None, :]
        logits, _, new_cache = forward(
            params, toks, arch, cache=cache, cache_index=idx,
            positions=positions, chunk_lengths=lens_b)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # (B, S)
        # position j verifies draft toks[:, j+1] (last column is junk and
        # masked off by draft_pos below)
        nxt = jnp.concatenate([toks[:, 1:], toks[:, :1]], axis=1)
        tt = jnp.maximum(temp, 1e-6)[:, None]                    # (B, 1)
        p = jax.nn.softmax(logits.astype(jnp.float32) / tt[..., None],
                           axis=-1)
        q = jnp.take_along_axis(p, nxt[..., None], axis=-1)[..., 0]
        keys = _lane_keys(key, seeds, ctrs)
        ks = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
        u = jax.vmap(lambda k: jax.random.uniform(k, (s,)))(ks[:, 0])
        acc = jnp.where((temp > 0)[:, None], u < q, nxt == greedy)
        jj = jnp.arange(s)[None, :]
        draft_pos = jj < (lens_b[:, None] - 1)
        run = jnp.cumprod(
            jnp.where(draft_pos, acc, True).astype(jnp.int32), axis=1)
        m = jnp.sum(run * draft_pos.astype(jnp.int32), axis=1)   # (B,)
        # final token at position m: correction (resample with the
        # rejected draft zeroed) or bonus (full target distribution)
        row = jnp.take_along_axis(logits, m[:, None, None], axis=1)[:, 0, :]
        row_greedy = jnp.argmax(row, axis=-1).astype(jnp.int32)
        rejected = m < (lens_b - 1)
        d_rej = jnp.take_along_axis(nxt, m[:, None], axis=1)[:, 0]
        row_f = row.astype(jnp.float32) / jnp.maximum(temp, 1e-6)[:, None]
        vocab = jnp.arange(row.shape[-1])[None, :]
        row_f = jnp.where(rejected[:, None] & (vocab == d_rej[:, None]),
                          jnp.asarray(-1e30, row_f.dtype), row_f)
        cand = jax.vmap(jax.random.categorical)(ks[:, 1],
                                                row_f).astype(jnp.int32)
        final = jnp.where(temp > 0, cand, row_greedy)
        emitted = jnp.where(jj < m[:, None], nxt, 0)
        emitted = jnp.where(jj == m[:, None], final[:, None], emitted)
        packed = jnp.concatenate(
            [emitted, (m + 1).astype(jnp.int32)[:, None]], axis=1)
        return packed, new_cache

    # shapes carry the bucket; it names the program (jit_verify_b<bucket>)
    fn.__name__ = f"verify_b{bucket}"
    return fn


@functools.lru_cache(maxsize=256)
def _verify_fn(arch: ArchConfig, bucket: int):
    """One compiled sampled-verify executable per (arch, bucket length) —
    only ever compiled when speculative decode runs with sampling (the
    greedy acceptance path reuses ``_prefill_fn`` outright)."""
    return jax.jit(_verify_raw(arch, bucket))


@dataclasses.dataclass
class ServeConfig:
    batch_slots: int = 8
    max_ctx: int = 2048
    temperature: float = 0.0
    cache_dtype: str = "float32"
    # GR-MAC backend override for CIM-enabled archs (None keeps the arch's
    # CIMConfig.backend; see kernels.dispatch for the choices). Decode is a
    # small-M matmul, so "auto" plans onto the batched-einsum xla path;
    # cim_tile_m / cim_tile_n pin the tiled/Pallas tile sizes when set.
    cim_backend: Optional[str] = None
    cim_tile_m: Optional[int] = None
    cim_tile_n: Optional[int] = None
    # Default EOS token id: a lane emitting it is finished and its slot is
    # freed immediately (per-request override via add_request(eos_id=...)).
    # None decodes every lane to max_ctx (the legacy behavior).
    eos_id: Optional[int] = None
    # "bucketed": chunked prefill, prompts padded to power-of-two buckets
    # (the default); "token": legacy one-dispatch-per-token prefill, kept
    # as the equivalence oracle for tests/benchmarks
    prefill_mode: str = "bucketed"
    prefill_bucket_min: int = 8
    prefill_bucket_max: int = 1024
    # Prefix cache (repro.serving.prefix_cache): byte budget for cached
    # prefill snapshots. When set, ``begin_request`` adopts the longest
    # cached prefix into the claimed lane (only the suffix is dispatched)
    # and ``advance_prefill`` stores new chunk-aligned boundaries.
    # Requires bucketed prefill. None disables caching entirely.
    prefix_cache_bytes: Optional[int] = None


class Engine:
    def __init__(self, arch: ArchConfig, params, cfg: ServeConfig,
                 prefix_cache: Optional["PrefixCache"] = None):
        assert arch.input_mode == "tokens", "engine serves token models"
        if cfg.cim_backend is not None:
            arch = arch.replace(cim=arch.cim.with_backend(cfg.cim_backend))
        if cfg.cim_tile_m is not None or cfg.cim_tile_n is not None:
            arch = arch.replace(cim=arch.cim.with_tiles(
                cfg.cim_tile_m, cfg.cim_tile_n))
        self.arch = arch
        self.cfg = cfg
        self.params = params
        self.cache = init_cache(
            arch, cfg.batch_slots, cfg.max_ctx, jnp.dtype(cfg.cache_dtype))
        self.lengths = np.zeros(cfg.batch_slots, np.int32)
        self.active = np.zeros(cfg.batch_slots, bool)
        self.tokens: List[List[int]] = [[] for _ in range(cfg.batch_slots)]
        # last emitted token per lane, fed back as next decode input
        self._last_host = np.zeros(cfg.batch_slots, np.int32)
        # per-slot EOS id (-1: none); seeded from cfg.eos_id per request
        self._eos = np.full(cfg.batch_slots, -1, np.int64)
        # per-slot SamplingParams state (seeded at begin_request):
        # temperature, PRNG seed (-1 unseeded) + sampling-event counter,
        # generated-token cap (-1 unlimited) + emitted count, spec_k
        # (-1: decoder default), and the terminal finish reason
        self._temp = np.full(cfg.batch_slots, cfg.temperature, np.float32)
        self._seed = np.full(cfg.batch_slots, -1, np.int64)
        self._ctr = np.zeros(cfg.batch_slots, np.int64)
        self._max_toks = np.full(cfg.batch_slots, -1, np.int64)
        self._emitted = np.zeros(cfg.batch_slots, np.int64)
        self._spec_k = np.full(cfg.batch_slots, -1, np.int64)
        self._finish_reason: List[Optional[str]] = \
            [None] * cfg.batch_slots
        # slots that have hosted a request (their cache state is dirty and
        # must be zeroed before reuse)
        self._dirty = np.zeros(cfg.batch_slots, bool)
        # slots claimed by a request whose prefill has not finished yet:
        # reserved (not claimable) but not in the decode batch either
        self._prefilling = np.zeros(cfg.batch_slots, bool)
        # per-slot prompt tokens not yet prefilled / last chunk's logits
        self._pending_prompt: Dict[int, List[int]] = {}
        self._pending_logits: Dict[int, jax.Array] = {}
        # slots completed outside step() (first prefill token == EOS),
        # surfaced through the next StepResult.finished
        self._pending_finished: List[int] = []
        # lazily-computed decode-phase energy report (None until asked)
        self._energy: Optional[dict] = None
        # Prefix cache: an explicit instance may be shared across engines
        # (cache-aware routing); cfg.prefix_cache_bytes builds a private
        # one. Chunk granularity MUST be the smallest prefill bucket so
        # every stored boundary composes with the existing power-of-two
        # bucket executables — zero new compiles on the hit path.
        if prefix_cache is None and cfg.prefix_cache_bytes is not None:
            prefix_cache = PrefixCache(cfg.prefix_cache_bytes,
                                       chunk_tokens=cfg.prefill_bucket_min)
        if prefix_cache is not None:
            if cfg.prefill_mode != "bucketed":
                raise ValueError(
                    "prefix cache requires prefill_mode='bucketed' (the "
                    "token path replays whole prompts)")
            if prefix_cache.chunk != cfg.prefill_bucket_min:
                raise ValueError(
                    f"prefix cache chunk {prefix_cache.chunk} != "
                    f"prefill_bucket_min {cfg.prefill_bucket_min}: cached "
                    "boundaries would not align with bucket executables")
        self.prefix_cache = prefix_cache
        # tokens adopted from the prefix cache for the slot's current
        # occupant (0 = cold prefill) — the scheduler's savings counter
        self._adopted = np.zeros(cfg.batch_slots, np.int64)
        # the caller's request id per slot (-1: none given), carried on the
        # trace spans of the slot's prefill (repro.serving.spans)
        self._rid = np.full(cfg.batch_slots, -1, np.int64)
        # prefill_tokens counts prompt tokens actually dispatched (suffix
        # only, under hits) — the CostLedger's prefill energy multiplier;
        # prefill_rows counts the rows those dispatches ran (batch_slots x
        # bucket each, every lane riding along), so tokens / rows is the
        # share of prefill work that carried a prompt token
        # (Scheduler.metrics reports both)
        self.stats = {"prefill_dispatches": 0, "decode_steps": 0,
                      "prefill_tokens": 0, "prefill_rows": 0,
                      "prefix_hit_tokens": 0,
                      # speculative-decode counters (speculative.py):
                      # dispatches by kind, iterations, and tokens
                      # emitted through spec steps (accepted incl. the
                      # correction/bonus token)
                      "draft_dispatches": 0, "verify_dispatches": 0,
                      "repair_dispatches": 0, "spec_steps": 0,
                      "spec_tokens": 0}

    # ------------------------------------------------------- compiled fns
    # Per-engine indirection over the shared executable caches: the single
    # seam through which every compiled dispatch flows, so the invariant
    # harness (repro.analysis.invariants) can interpose counters without
    # touching the hot-path call sites.
    def _compiled_decode(self, sample: bool):
        return _decode_fn(self.arch, sample)

    def _compiled_prefill(self, bucket: int):
        return _prefill_fn(self.arch, bucket)

    def _compiled_draft(self, draft_arch: ArchConfig):
        # the draft is a plain greedy decode of the (cheap) draft arch
        # over the SAME weights and cache — when draft_arch == self.arch
        # this is literally the serving decode executable (zero new
        # compiles); otherwise it is the draft arch's one decode compile
        return _decode_fn(draft_arch, False)

    def _compiled_verify(self, bucket: int):
        return _verify_fn(self.arch, bucket)

    @staticmethod
    def _snapshot(host_state: np.ndarray) -> jax.Array:
        """Immutable device view of mutable per-slot host state.

        ``jnp.asarray(numpy_array)`` is zero-copy on CPU when the buffer is
        aligned, so the jax Array *aliases* ``self.lengths``/``self.active``.
        The engine mutates those in place right after dispatching the decode
        — which executes asynchronously — so without a defensive copy the
        computation can read the post-increment value and write the KV cache
        at the wrong slot position (rare, load-dependent token corruption).
        """
        return jnp.asarray(host_state.copy())

    # ------------------------------------------------------------ params
    @staticmethod
    def _resolve_params(eos_id: Optional[int],
                        params: Optional[SamplingParams],
                        stacklevel: int = 4) -> SamplingParams:
        """Fold the legacy ``eos_id=`` kwarg into ``SamplingParams`` (one
        release of ``DeprecationWarning``; passing both is an error).
        ``params=None`` with no legacy kwargs is the silent default."""
        if params is None:
            if eos_id is not None:
                warnings.warn(
                    "eos_id= is deprecated: pass "
                    "params=SamplingParams(eos_id=...) instead (the "
                    "behavior is identical)", DeprecationWarning,
                    stacklevel=stacklevel)
            return SamplingParams(eos_id=eos_id)
        if eos_id is not None:
            raise ValueError(
                "pass eos_id via SamplingParams, not alongside params=")
        return params

    # ------------------------------------------------------------ prefill
    def add_request(self, prompt: List[int],
                    eos_id: Optional[int] = None,
                    key: Optional[jax.Array] = None, *,
                    params: Optional[SamplingParams] = None) -> int:
        """Prefill a free slot, sample the first output token from the
        prefill logits, and return the slot id.

        Bucketed mode splits the prompt into ``prefill_bucket_max``-sized
        chunks, pads the remainder up to a power of two, and issues one
        compiled dispatch per chunk — ``ceil(len / bucket_max)`` dispatches
        (never more than ``ceil(log2(len)) + 1`` for prompts that fit the
        context), vs ``len`` in legacy ``prefill_mode="token"``.

        Both modes adopt the last-valid-token logits to produce the first
        generated token here (appended to ``tokens[slot]``), so the first
        ``step`` feeds *that* token — no decode dispatch ever re-feeds the
        last prompt token, whose K/V used to be written twice (at len-1
        and len). Pass ``key`` to sample it when ``temperature > 0``
        (greedy argmax otherwise, exactly like ``step``). A first token
        that hits the request's EOS finishes the request immediately (the
        slot never joins the decode batch and is free to reuse).

        ``params`` (``SamplingParams``) is the request-level entry point:
        temperature / seed / eos_id / max_tokens / spec_k, each ``None``
        field inheriting the engine default. The positional ``eos_id``
        kwarg is the deprecated legacy spelling (one release of
        ``DeprecationWarning``; identical behavior).

        Sampling: with ``temperature > 0`` the first token is sampled
        **only when** ``key`` is passed; ``temperature > 0`` without a
        key falls back to greedy argmax with a ``UserWarning`` (the
        explicit form of what used to happen silently — ``step`` applies
        the same key-gated rule). A request with ``params.seed`` set
        samples from its own seeded stream, no per-step key needed.
        """
        params = self._resolve_params(eos_id, params)
        slot = self.begin_request(prompt, params=params)
        if self.cfg.prefill_mode == "token":
            sample = self._resolve_sampling(key, slot)
            self._pending_prompt.pop(slot, None)
            for t in prompt[:-1]:
                self._advance_slot(slot, t)
            # the final dispatch's ids ARE the last-valid-token selection
            first = self._advance_slot(slot, prompt[-1], sample=sample,
                                       key=key)
            self._adopt_first_token(slot, first)
        else:
            while self.prefill_remaining(slot):
                self.advance_prefill(slot)
            self.finish_prefill(slot, key=key)
        return slot

    # ------------------------------------------------- incremental prefill
    def begin_request(self, prompt: List[int],
                      eos_id: Optional[int] = None, *,
                      params: Optional[SamplingParams] = None,
                      rid: Optional[int] = None) -> int:
        """Claim and validate a free slot for ``prompt`` without running
        any prefill: the lane is *reserved* (``free_slots`` excludes it)
        but not yet in the decode batch. The scheduler drains the prompt
        through ``advance_prefill`` between decode steps and activates the
        lane with ``finish_prefill``; ``add_request`` is the blocking
        begin → advance-until-drained → finish composition of the same
        methods. ``params`` seeds the lane's per-request state (see
        ``add_request``); the ``eos_id`` kwarg is the deprecated legacy
        spelling. ``rid``, the caller's request id, only labels the lane's
        trace spans."""
        params = self._resolve_params(eos_id, params)
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) >= self.cfg.max_ctx:
            # strictly less: the first decode step writes the first
            # *generated* token's K/V at position len(prompt), which must
            # still be a valid cache index (at len == max_ctx it would
            # clamp onto the last prompt entry and corrupt the lane)
            raise ValueError(
                f"prompt of {len(prompt)} tokens needs max_ctx > "
                f"{len(prompt)} (got {self.cfg.max_ctx}) to decode")
        free = np.where(~self.active & ~self._prefilling)[0]
        if len(free) == 0:
            raise RuntimeError("no free slots")
        slot = int(free[0])
        if self._dirty[slot]:
            self._reset_slot_state(slot)
        self._dirty[slot] = True
        self.tokens[slot] = list(prompt)
        self.lengths[slot] = 0
        self._prefilling[slot] = True
        self._pending_prompt[slot] = list(prompt)
        self._pending_logits.pop(slot, None)
        eos = params.eos_id if params.eos_id is not None else self.cfg.eos_id
        self._eos[slot] = -1 if eos is None else int(eos)
        self._temp[slot] = (self.cfg.temperature
                            if params.temperature is None
                            else params.temperature)
        self._seed[slot] = -1 if params.seed is None else int(params.seed)
        self._ctr[slot] = 0
        self._max_toks[slot] = (-1 if params.max_tokens is None
                                else int(params.max_tokens))
        self._emitted[slot] = 0
        self._spec_k[slot] = (-1 if params.spec_k is None
                              else int(params.spec_k))
        self._finish_reason[slot] = None
        self._adopted[slot] = 0
        self._rid[slot] = -1 if rid is None else int(rid)
        if self.prefix_cache is not None:
            hit = self.prefix_cache.lookup(prompt)
            if hit is not None:
                # adopt the cached prefix into the (just-zeroed) lane:
                # device-side restore, then prefill only the suffix from
                # cache index P — same bucket executables, no new compiles
                p, snap = hit
                self.cache = restore_slot(self.cache, slot, p, snap)
                self.lengths[slot] = p
                self._pending_prompt[slot] = list(prompt[p:])
                self._adopted[slot] = p
                self.stats["prefix_hit_tokens"] += p
        return slot

    def prefill_remaining(self, slot: int) -> int:
        """Prompt tokens of ``slot`` not yet prefilled (0 once drained)."""
        return len(self._pending_prompt.get(slot, ()))

    def adopted_prefix(self, slot: int) -> int:
        """Prompt tokens the slot's current occupant adopted from the
        prefix cache at ``begin_request`` (0 = cold prefill). The
        scheduler reads this right after admission for its
        prefill-tokens-saved / recompute-savings accounting."""
        return int(self._adopted[slot])

    def advance_prefill(self, slot: int,
                        max_tokens: Optional[int] = None) -> int:
        """One bucketed chunk dispatch for a mid-prefill slot: consumes
        ``min(remaining, prefill_bucket_max, max_tokens)`` prompt tokens
        through the shared power-of-two bucket executables (a budget-
        truncated chunk pads up to the next bucket, so interleaving never
        compiles anything the blocking path would not). Returns the number
        of tokens consumed; the chunk's last-valid-token logits are kept
        on device for ``finish_prefill``."""
        rem = self._pending_prompt[slot]
        take = min(len(rem), self.cfg.prefill_bucket_max)
        if max_tokens is not None:
            take = min(take, int(max_tokens))
        if take <= 0:
            return 0
        pc = self.prefix_cache
        if pc is not None and take < len(rem):
            # a further chunk follows anyway: shrink this one so it ends
            # on a cache-chunk boundary (snapshots exist only there). The
            # truncated chunk pads to a smaller-or-equal power-of-two
            # bucket, so no new executable is introduced.
            aligned = take - (int(self.lengths[slot]) + take) % pc.chunk
            if aligned >= 1:
                take = aligned
        self._pending_logits[slot] = self._prefill_chunk(slot, rem[:take])
        del rem[:take]
        if pc is not None:
            done = int(self.lengths[slot])
            if done > 0 and done % pc.chunk == 0:
                # capture the boundary live (recurrent state at an
                # interior length is unrecoverable later); insert() skips
                # the snapshot thunk when the boundary is already stored
                pc.insert(self.tokens[slot][:done],
                          lambda: snapshot_slot(self.cache, slot, done))
        return take

    def finish_prefill(self, slot: int,
                       key: Optional[jax.Array] = None) -> int:
        """Select the first output token from the final chunk's logits and
        activate the lane for decode (or finish it immediately when that
        token is the request's EOS — see ``add_request``). Requires the
        prompt fully drained. Applies the documented sampling contract:
        ``temperature > 0`` without ``key`` warns and falls back to greedy
        argmax."""
        if self.prefill_remaining(slot):
            raise RuntimeError(
                f"slot {slot}: {self.prefill_remaining(slot)} prompt "
                "tokens still pending — drain with advance_prefill first")
        sample = self._resolve_sampling(key, slot)
        logits = self._pending_logits.pop(slot)
        del self._pending_prompt[slot]
        with span("engine.first_token", slot=slot,
                  rid=int(self._rid[slot])):
            first = self._select_token(logits, slot, sample, key)
        self._adopt_first_token(slot, first)
        return first

    def _adopt_first_token(self, slot: int, first: int) -> None:
        """Shared end-of-prefill bookkeeping: record the first generated
        token and either join the decode batch or finish at once (first
        token == EOS, or ``max_tokens == 1``: the slot never joins a
        decode batch, so the completion is surfaced through the next
        ``StepResult.finished``)."""
        self.tokens[slot].append(first)
        self._last_host[slot] = first
        self._prefilling[slot] = False
        self._emitted[slot] = 1
        if self._eos[slot] >= 0 and first == self._eos[slot]:
            self.active[slot] = False
            self._finish_reason[slot] = "eos"
            self._pending_finished.append(slot)
        elif 0 <= self._max_toks[slot] <= 1:
            self.active[slot] = False
            self._finish_reason[slot] = "length"
            self._pending_finished.append(slot)
        else:
            self.active[slot] = True

    def finish_reason(self, slot: int) -> Optional[str]:
        """Terminal reason recorded when the engine froze the lane
        (``"eos"`` / ``"length"`` / ``"ctx"``); None while live or when
        the slot was freed externally (``release_slot``)."""
        return self._finish_reason[slot]

    def release_slot(self, slot: int) -> None:
        """Free a lane regardless of progress — the scheduler's stop seam
        (request hit its ``max_new_tokens``; preemption under overload).
        Mid-prefill state is discarded; the dirty flag stays set so the
        next claim zeroes the lane's recurrent cache state."""
        self.active[slot] = False
        self._prefilling[slot] = False
        self._pending_prompt.pop(slot, None)
        self._pending_logits.pop(slot, None)

    def free_slots(self) -> int:
        """Slots claimable by ``begin_request``/``add_request`` right now
        (neither decoding nor mid-prefill) — the admission-control count."""
        return int(np.sum(~self.active & ~self._prefilling))

    def _resolve_sampling(self, key: Optional[jax.Array],
                          slot: int) -> bool:
        """The per-request sampling rule: sample iff the slot's
        temperature is ``> 0`` AND entropy is available — a per-call key,
        or the request's own ``SamplingParams.seed``. The no-key no-seed
        fallback to greedy is explicit: it warns instead of silently
        diverging from what a ``temperature > 0`` caller expects."""
        if self._temp[slot] <= 0:
            return False
        if self._seed[slot] >= 0:
            return True
        if key is None:
            warnings.warn(
                "temperature > 0 but no PRNG key passed: falling back to "
                "greedy argmax for this token. Pass key= to sample "
                "(Engine.step applies the same key-gated rule), or set "
                "SamplingParams.seed for a self-seeded request.",
                UserWarning, stacklevel=3)
            return False
        return True

    def _effective_temps(self, key: Optional[jax.Array]) -> np.ndarray:
        """Per-lane temperatures actually in effect for one sampling
        event: a lane samples iff its temperature is positive AND it has
        entropy (the caller's key, or its own seed) — lanes without are
        clamped to 0 (argmax) inside the sampled executable."""
        if key is not None:
            return self._temp.astype(np.float32)
        return np.where(self._seed >= 0, self._temp, 0.0).astype(np.float32)

    def _sampling_args(self, key: Optional[jax.Array],
                       eff: np.ndarray) -> tuple:
        """The (key, temp, seeds, ctrs) tail of every sampled executable
        call, snapshotted against async mutation like ``_snapshot``."""
        return (key if key is not None else jax.random.PRNGKey(0),
                jnp.asarray(eff.copy()),
                jnp.asarray(self._seed.astype(np.int32)),
                jnp.asarray(self._ctr.astype(np.int32)))

    def _count_sampling_event(self, eff: np.ndarray,
                              lanes: np.ndarray) -> None:
        """Advance the sampling-event counter of every seeded lane that
        just consumed randomness (its stream is ``fold_in(seed, ctr)``
        per event, so placement and co-traffic can never perturb it)."""
        self._ctr[lanes & (eff > 0) & (self._seed >= 0)] += 1

    def _select_token(self, logits_dev: jax.Array, slot: int,
                      sample: bool, key: Optional[jax.Array]) -> int:
        """Token selection over prefill logits (B, V), mirroring the fused
        decode's math exactly (per-lane keys + categorical / argmax with
        per-lane temperatures) so token-mode and bucketed-mode prefill
        stay equivalent. Routed through ``_fetch`` — the engine's single
        transfer point."""
        if sample:
            eff = self._effective_temps(key)
            k, temps, seeds, ctrs = self._sampling_args(key, eff)
            keys = _lane_keys(k, seeds, ctrs)
            ids = jax.vmap(
                lambda kk, lg, tt: jax.random.categorical(
                    kk, lg / jnp.maximum(tt, 1e-6)))(keys, logits_dev,
                                                     temps)
            ids = jnp.where(jnp.asarray(eff) > 0, ids,
                            jnp.argmax(logits_dev, axis=-1))
            lane = np.zeros(self.cfg.batch_slots, bool)
            lane[slot] = True
            self._count_sampling_event(eff, lane)
        else:
            ids = jnp.argmax(logits_dev, axis=-1)
        return int(self._fetch(ids.astype(jnp.int32))[slot])

    def _reset_slot_state(self, slot: int):
        """Zero one lane's cache before a freed slot hosts a new request.

        Attention KV is positionally overwritten and length-masked, so it
        cannot leak — but RG-LRU/SSM recurrent states persist across the
        request boundary and would seed the new prompt's prefill scan with
        the previous occupant's state."""
        def z(axis):
            def f(a):
                idx = [slice(None)] * a.ndim
                idx[axis] = slot
                return a.at[tuple(idx)].set(0)
            return f
        with span("engine.reset_lane", slot=slot):
            out = dict(self.cache)
            if "superblocks" in out:
                out["superblocks"] = jax.tree.map(z(1), out["superblocks"])
            if "tail" in out:
                out["tail"] = jax.tree.map(z(0), out["tail"])
            self.cache = out

    def _bucket(self, n: int) -> int:
        b = self.cfg.prefill_bucket_min
        while b < n:
            b *= 2
        return b

    def _prefill_chunk(self, slot: int, chunk: List[int]) -> jax.Array:
        """One bucketed prefill dispatch: the chunk is right-padded to its
        bucket and every other lane rides along frozen (length 0), so the
        returned cache is adopted wholesale — no merge. Returns the
        last-valid-token logits (B, V) on device (the final chunk's feed
        the first-output-token selection)."""
        bucket = self._bucket(len(chunk))
        with span("engine.prefill_chunk", slot=slot,
                  rid=int(self._rid[slot]), tokens=len(chunk),
                  bucket=bucket):
            toks = np.zeros((self.cfg.batch_slots, bucket), np.int32)
            toks[slot, :len(chunk)] = chunk
            lens = np.zeros(self.cfg.batch_slots, np.int32)
            lens[slot] = len(chunk)
            fill = self._compiled_prefill(bucket)
            logits, _, self.cache = fill(
                self.params, jnp.asarray(toks), self.cache,
                self._snapshot(self.lengths), jnp.asarray(lens))
            self.lengths[slot] += len(chunk)
            self.stats["prefill_dispatches"] += 1
            self.stats["prefill_tokens"] += len(chunk)
            self.stats["prefill_rows"] += self.cfg.batch_slots * bucket
        return logits

    def _advance_slot(self, slot: int, token: int, sample: bool = False,
                      key: Optional[jax.Array] = None) -> int:
        # Legacy token-by-token prefill: a batched decode call with per-slot
        # indices, all lanes but ``slot`` masked out of the cache merge.
        # Returns this slot's selected next token (meaningful on the final
        # prompt token, where it is the first generated token).
        toks = np.zeros((self.cfg.batch_slots, 1), np.int32)
        toks[slot, 0] = token
        mask = np.zeros(self.cfg.batch_slots, bool)
        mask[slot] = True
        eff = self._effective_temps(key) if sample else \
            np.zeros(self.cfg.batch_slots, np.float32)
        ids, self.cache = self._compiled_decode(sample)(
            self.params, jnp.asarray(toks), self.cache,
            self._snapshot(self.lengths), jnp.asarray(mask),
            *self._sampling_args(key, eff))
        if sample:
            self._count_sampling_event(eff, mask)
        self.lengths[slot] += 1
        self.stats["prefill_dispatches"] += 1
        self.stats["prefill_tokens"] += 1
        self.stats["prefill_rows"] += self.cfg.batch_slots
        return int(self._fetch(ids)[slot])

    # ------------------------------------------------------------ decode
    def step(self, key: Optional[jax.Array] = None) -> "StepResult":
        """One decode step for every active slot.

        The compiled decode returns only the sampled token ids; everything
        else (logits, cache merge, sampling) stays on device. Pass ``key``
        (and set ``temperature > 0``) for per-lane categorical sampling;
        greedy argmax otherwise.

        Returns a ``StepResult`` (a dict of slot id -> token, exactly as
        before) whose ``finished`` attribute lists the slots freed this
        step — lanes that emitted their EOS or ran out of context — and
        whose ``pj_per_token`` carries the decode-phase CIM energy per
        generated token (ledger-derived, see ``energy_per_token``; None
        when the arch serves without the CIM path). Freed slots drop out
        of the active mask (their caches freeze inside the fused decode)
        and are immediately claimable by ``add_request``. Requests that
        completed during ``add_request`` itself (first prefill-sampled
        token == EOS) are reported here too, ahead of this step's frees.
        """
        with span("engine.step"):
            return self._step(key)

    def _step(self, key: Optional[jax.Array]) -> "StepResult":
        pending, outputs = self._drain_pending()
        if not self.active.any():
            return StepResult({}, pending, self._pj_per_token,
                              outputs=outputs)
        eff = self._effective_temps(key)
        sample = bool((eff[self.active] > 0).any())
        fn = self._compiled_decode(sample)
        ids_dev, self.cache = fn(
            self.params, self._snapshot(self._last_host[:, None]),
            self.cache, self._snapshot(self.lengths),
            self._snapshot(self.active),
            *self._sampling_args(key, eff))
        if sample:
            self._count_sampling_event(eff, self.active)
        ids = self._fetch(ids_dev)
        act = np.where(self.active)[0]
        out = {}
        for s in act:
            t = int(ids[s])
            self.tokens[s].append(t)
            out[int(s)] = t
        self._last_host[act] = ids[act]
        self.lengths[act] += 1
        self._emitted[act] += 1
        # Per-slot completion: emitted EOS, hit the request's max_tokens,
        # or no context left for another decode write. Either way the slot
        # leaves the active mask (its cache freezes in the next fused
        # decode) and is free to reuse.
        hit_eos = (self._eos >= 0) & (self._last_host == self._eos)
        maxed = (self._max_toks >= 0) & (self._emitted >= self._max_toks)
        done = self.active & (hit_eos | maxed
                              | (self.lengths >= self.cfg.max_ctx))
        for s in act:
            reason = None
            if done[s]:
                reason = ("eos" if hit_eos[s]
                          else "length" if maxed[s] else "ctx")
                self._finish_reason[s] = reason
            outputs.append(RequestOutput(
                slot=int(s), tokens=[out[int(s)]], finished=bool(done[s]),
                finish_reason=reason, _energy_fn=self._pj_per_token))
        finished = pending + [int(s) for s in np.where(done)[0]]
        self.active[done] = False
        self.stats["decode_steps"] += 1
        return StepResult(out, finished, self._pj_per_token,
                          outputs=outputs)

    def _drain_pending(self):
        """Pop completions recorded outside ``step`` (first prefill token
        hit EOS / a one-token ``max_tokens`` cap) as (slot ids, their
        token-less ``RequestOutput`` records)."""
        pending, self._pending_finished = self._pending_finished, []
        outputs = [RequestOutput(slot=s, tokens=[], finished=True,
                                 finish_reason=self._finish_reason[s],
                                 _energy_fn=self._pj_per_token)
                   for s in pending]
        return pending, outputs

    # ------------------------------------------------- speculative seams
    # Orchestrated by repro.serving.speculative.SpecDecoder; kept on the
    # engine because they touch cache/state internals and must flow
    # through the instrumented _compiled_*/_fetch seams.

    _SPEC_STATE_KINDS = ("local", "rglru", "ssm")

    def spec_snapshot(self) -> dict:
        """References to the rollback-sensitive cache subtrees, whole
        batch: local-attn ring buffers (drafting overwrites ring slots
        that alias *valid older* positions) and RG-LRU/SSM recurrent +
        conv states (mutated by every pass). jax arrays are immutable and
        dispatches REPLACE ``self.cache`` leaves, so this is O(1)
        bookkeeping — no copy, no transfer. Global-attn KV needs no
        snapshot: rows past a lane's committed length are causally masked
        (decode: ``slot <= idx``; prefill: ``q_pos >= k_pos``) and are
        positionally overwritten before the length ever reaches them, so
        draft/verify pollution there is invisible by construction. An
        empty dict therefore means the arch is rollback-free."""
        snap = {}
        for group in ("superblocks", "tail"):
            g = self.cache.get(group)
            if not g:
                continue
            kept = {name: layer for name, layer in g.items()
                    if name.split("_", 1)[1] in self._SPEC_STATE_KINDS}
            if kept:
                snap[group] = kept
        return snap

    def spec_restore(self, snap: dict, lanes: np.ndarray) -> None:
        """Restore a ``spec_snapshot`` into the lanes where ``lanes`` is
        True (device-side per-lane where-merge, same layout rules as
        ``_merge_cache``); other lanes keep their current state. Free of
        device→host traffic — the merge rides into the next dispatch."""
        if not snap:
            return
        mask = jnp.asarray(lanes.copy())

        def mrg(axis):
            def f(cur, old):
                shape = [1] * cur.ndim
                shape[axis] = -1
                return jnp.where(jnp.reshape(mask, shape), old, cur)
            return f

        out = dict(self.cache)
        for group, axis in (("superblocks", 1), ("tail", 0)):
            if group not in snap:
                continue
            newg = dict(out[group])
            for name, layer in snap[group].items():
                newg[name] = jax.tree.map(mrg(axis), newg[name], layer)
            out[group] = newg
        self.cache = out

    def draft_step(self, draft_arch: ArchConfig, cur: np.ndarray,
                   mask: np.ndarray, offsets: np.ndarray) -> np.ndarray:
        """One greedy draft decode dispatch of ``draft_arch`` (same
        weights, shared cache) for the lanes in ``mask``, feeding token
        ``cur[i]`` at cache offset ``offsets[i]``. Non-mask lanes are
        frozen by the in-executable merge exactly like inactive decode
        lanes. Returns the drafted ids (one fetch)."""
        with span("engine.draft"):
            toks = np.zeros((self.cfg.batch_slots, 1), np.int32)
            toks[mask, 0] = cur[mask]
            zero = np.zeros(self.cfg.batch_slots, np.float32)
            ids_dev, self.cache = self._compiled_draft(draft_arch)(
                self.params, jnp.asarray(toks), self.cache,
                self._snapshot(offsets.astype(np.int32)),
                jnp.asarray(mask.copy()), *self._sampling_args(None, zero))
            self.stats["draft_dispatches"] += 1
            return self._fetch(ids_dev)

    def verify_chunk(self, chunk: np.ndarray,
                     lens: np.ndarray) -> np.ndarray:
        """Exact greedy verification of ``chunk`` — per lane
        ``[pending_token, d_1 .. d_{k-1}]`` with ``lens`` valid entries
        (0 freezes the lane bitwise) — through the *existing* bucketed
        prefill executable: ``prefill_step`` already returns per-position
        argmax ids, so the verifier costs zero new compiles. Returns the
        (B, bucket) target ids (one fetch); the host keeps the longest
        draft prefix matching them. Lengths are NOT committed here — the
        orchestrator commits only accepted tokens."""
        with span("engine.verify"):
            b, k = chunk.shape
            bucket = self._bucket(k)
            toks = np.zeros((b, bucket), np.int32)
            toks[:, :k] = chunk
            _, ids_dev, self.cache = self._compiled_prefill(bucket)(
                self.params, jnp.asarray(toks), self.cache,
                self._snapshot(self.lengths),
                jnp.asarray(lens.astype(np.int32).copy()))
            self.stats["verify_dispatches"] += 1
            return self._fetch(ids_dev)[:, :k]

    def verify_chunk_sampled(self, chunk: np.ndarray, lens: np.ndarray,
                             key: Optional[jax.Array]):
        """Rejection-rule verification of ``chunk`` under per-lane
        temperatures (``_verify_raw`` carries the acceptance math and the
        unbiasedness argument). Returns ``(emitted, counts)`` — emitted
        tokens (B, bucket) with ``counts[i]`` valid entries per lane —
        from the packed single fetch. Lanes with ``temp <= 0`` get exact
        greedy acceptance, so mixed batches verify in one dispatch."""
        with span("engine.verify"):
            b, k = chunk.shape
            bucket = self._bucket(k)
            toks = np.zeros((b, bucket), np.int32)
            toks[:, :k] = chunk
            eff = self._effective_temps(key)
            packed_dev, self.cache = self._compiled_verify(bucket)(
                self.params, jnp.asarray(toks), self.cache,
                self._snapshot(self.lengths),
                jnp.asarray(lens.astype(np.int32).copy()),
                *self._sampling_args(key, eff))
            self._count_sampling_event(eff, lens > 0)
            self.stats["verify_dispatches"] += 1
            packed = self._fetch(packed_dev)
            return packed[:, :k], packed[:, bucket]

    def repair_chunk(self, chunk: np.ndarray, lens: np.ndarray,
                     index: np.ndarray) -> None:
        """Partial-acceptance repair: after ``spec_restore`` rolled the
        rollback-sensitive state of partially-accepted lanes back to the
        pre-draft snapshot, re-feed each such lane's *accepted* prefix
        (``lens[i]`` leading tokens of ``chunk``, 0 = frozen) at its
        pre-verify offset ``index``. Same bucket executable as the
        verify; logits and ids are discarded on device — acceptance
        already knows every token, so repair adds NO fetch (the invariant
        ``run_spec_invariants`` checks)."""
        with span("engine.repair"):
            b, k = chunk.shape
            bucket = self._bucket(k)
            toks = np.zeros((b, bucket), np.int32)
            toks[:, :k] = chunk
            _, _, self.cache = self._compiled_prefill(bucket)(
                self.params, jnp.asarray(toks), self.cache,
                self._snapshot(index.astype(np.int32)),
                jnp.asarray(lens.astype(np.int32).copy()))
            self.stats["repair_dispatches"] += 1

    # ------------------------------------------------------------ energy
    def energy_per_token(self) -> Optional[dict]:
        """Decode-phase energy report for this engine's served arch: the
        ``core.costs`` ledger of one decode step priced per site, per
        generated token. Computed lazily once per engine (a shape-only
        trace + the memoized ENOB solve); None when the arch's CIM path
        is off."""
        if not self.arch.cim.enabled:
            return None
        if self._energy is None:
            self._energy = costs.price_ledger(
                costs.trace_decode(self.arch), 1)
            self.stats["pj_per_token"] = self._energy["pj_per_token"]
        return self._energy

    def _pj_per_token(self) -> Optional[float]:
        rep = self.energy_per_token()
        return None if rep is None else rep["pj_per_token"]

    @staticmethod
    def _fetch(ids_dev: jax.Array) -> np.ndarray:
        """The single device→host transfer per compiled dispatch that
        needs one: a (batch_slots,) int32 id array per decode/draft step
        and prefill first-token selection, or a (batch_slots, bucket[+1])
        int32 array per speculative verify. Repair dispatches cross
        nothing."""
        with span("engine.fetch"):
            return np.asarray(ids_dev)


def energy_report(arch: ArchConfig, *, batch: int = 1,
                  prefill_bucket: int = 128,
                  train_seq: Optional[int] = None,
                  seed: int = 0, n_cols: int = 1 << 11) -> dict:
    """Ledger-derived CIM energy report (pJ/token) for all three phases.

    Traces the *real* model functions — ``prefill_step`` (one
    ``prefill_bucket``-token dispatch), the decode step, and the train
    step — into ``core.costs.CostLedger``s and prices every recorded
    contract at its site's resolved design (``CIMConfig.for_site``), so
    mixed per-site deployments (``site_overrides``) and per-phase shape
    differences are priced faithfully and the numbers can never drift
    from the model code. Top-level keys alias the decode phase (the
    deployment metric the paper optimizes); ``phases`` carries the full
    per-phase, per-site breakdown. ``seed``/``n_cols`` configure the
    underlying Monte-Carlo ENOB solve (both participate in its
    memoization key).
    """
    if not arch.cim.enabled:
        return {"enabled": False}
    phases = costs.phase_report(arch, batch=batch,
                                prefill_bucket=prefill_bucket,
                                train_seq=train_seq, seed=seed,
                                n_cols=n_cols)
    dec = phases["decode"]
    return {
        "enabled": True,
        "phases": phases,
        # decode-phase aliases: the legacy per-decoded-token metric
        "fj_per_op": dec["fj_per_op"],
        "conventional_fj_per_op": dec["conventional_fj_per_op"],
        "ops_per_token": dec["ops_per_token"],
        "analog_ops_per_token": dec["analog_ops_per_token"],
        "pj_per_token": dec["pj_per_token"],
        "sites": dec["sites"],
    }
