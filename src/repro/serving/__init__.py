"""Serving stack: the slot ``Engine``, the ``PrefixCache`` beside it,
and the continuous-batching ``Scheduler`` above both.

Three layers, two seams
-----------------------
* ``engine``    — mechanism. Fixed-size decode batch ("slots"), bucketed
  chunked prefill (one compiled dispatch per power-of-two chunk), fused
  on-device sampling (exactly one device→host transfer per decode step),
  per-slot EOS freeing, and ledger-derived pJ/token
  (``StepResult.pj_per_token``). The incremental prefill API
  (``begin_request`` / ``advance_prefill`` / ``finish_prefill`` /
  ``release_slot`` / ``free_slots``) is the scheduler seam:
  ``add_request`` is the blocking composition of the same methods.
* ``prefix_cache`` — reuse. A trie over token-id chunks caching per-slot
  prefill snapshots so shared system prompts prefill once.
* ``scheduler`` — policy. FIFO (or shortest-prompt-first with an
  anti-starvation age bound) queue with WAITING → PREFILLING →
  RUNNING → FINISHED states (plus PREEMPTED under overload), admission
  control against free slots and ``max_ctx``, chunked prefill
  interleaved into decode iterations under a per-step token budget, and
  per-request TTFT/TPOT/pJ-per-token accounting with SLO-conditioned
  goodput. See ``scheduler``'s module docstring for the state machine,
  budget semantics, preemption policy, and goodput definitions.

Prefix-cache design note
------------------------
``ServeConfig.prefix_cache_bytes`` turns reuse on; the essentials
(full detail in ``prefix_cache``'s module docstring):

* **Key alignment.** Trie edges are ``prefill_bucket_min``-token
  chunks, so every cached boundary is a length the existing
  power-of-two bucket executables already serve — adopting a prefix and
  prefilling the suffix introduces **zero new compiles**, and the
  compile-budget / one-transfer invariants are re-proven under a
  hit-heavy trace (``repro.analysis.invariants.run_prefix_invariants``).
* **Snapshot layout per arch family.** Snapshots mirror the engine
  cache pytree with the slot lane extracted: ``attn`` layers store K/V
  rows ``[:P]`` (sliceable to any shorter shared prefix for
  pure-attention archs — RadixAttention-style subsumption); ``local``
  ring buffers are copied whole (validity re-derives from the restored
  length); ``rglru``/``ssm`` store the recurrent state + conv tail — a
  few KB per prefix regardless of its length, the fixed-state economy
  the GPU paged-KV stacks don't have. Capture and restore are
  device-side (no host crossing).
* **Eviction.** One LRU over snapshot entries under the byte budget;
  hits refresh recency, evicted entries prune their trie path, counters
  (hits/misses/inserts/evictions/bytes) are exact-gated in CI.
* **Exactness contract.** Snapshots are captured live at chunk-aligned
  boundaries during prefill; bucketed==token chunking equivalence makes
  a restored prefix bit-identical to a cold lane, so hit streams equal
  cold-prefill streams exactly (tested across attn/rglru/ssm/moe).
  Lookup always leaves ≥1 suffix token so ``finish_prefill`` has real
  last-token logits.

Follow-up (ROADMAP item 2): block/paged KV layout so attention restores
stop copying dense lanes, then disaggregated prefill/decode engines
with explicit KV/state handoff.

Request API
-----------
``SamplingParams`` is the single typed entry for per-request knobs
(temperature, seed, eos_id, max_tokens, spec_k) — passed to
``Engine.add_request`` / ``begin_request`` and ``Scheduler.submit`` as
``params=``; the legacy ``eos_id=`` / ``max_new_tokens=`` kwargs
convert bit-identically for one release under a ``DeprecationWarning``.
Results stream back as typed ``RequestOutput`` records on
``StepResult.outputs`` — per-request tokens, finish flag, finish reason
(``"eos"`` / ``"length"`` / ``"ctx"``), and lazy pJ/token — the one
shape engine, scheduler, bench, and ``launch/serve.py`` all consume.

Speculative-decode design note
------------------------------
``speculative.SpecDecoder`` wraps an engine and emits up to ``k``
tokens per iteration: draft ``k - 1`` with a cheap CIM config of the
*same* model on the *same* cache (no second model, no draft prefill),
verify all of them in ONE chunked dispatch through the exact grmac
path — the existing bucketed prefill executables are the verifier, so
greedy verification adds **zero new compiles** — and keep the longest
accepted prefix. Greedy acceptance is bit-identical to sequential
decode across attn/rglru/ssm/moe for any drafter; sampled acceptance
applies the standard rejection rule on device (unbiased, seeded).
Recurrent archs roll back via O(1) ``spec_snapshot`` refs + a
device-side per-lane restore, then one fetch-free repair dispatch
re-feeds accepted prefixes; global-attention KV needs no rollback at
all. ``speculative.price_speculation`` prices draft + verify against
sequential decode on the CostLedger (pJ/accepted-token), asking whether
speculation is an energy win and not just a latency win. Full detail
in ``speculative``'s module docstring;
``repro.analysis.invariants.run_spec_invariants`` machine-checks the
compile/transfer claims.

Benchmarks: ``benchmarks/serve_bench.py`` (fixed-batch TTFT/TPOT),
``benchmarks/traffic_bench.py`` (open-loop Poisson + closed-loop
fixed-concurrency traffic: goodput vs arrival rate, saturation knee,
continuous vs static batching, shared-prefix cache-on vs cache-off),
``benchmarks/spec_bench.py`` (sequential vs speculative per cache
family: accepted-tokens/step, TTLT speedup, pJ/accepted-token verdict).
Invariants: ``repro.analysis.invariants`` proves the compile budget and
one-transfer-per-step rules hold under hand-placed, scheduler-driven,
prefix-hit-heavy, and speculative serving.

Tracing: ``spans`` puts a ``repro.*`` host span at each layer boundary
(scheduler step and phases, the engine's dispatches and its one fetch)
into ``jax.profiler``'s trace, on the device's clock.
"""
from repro.serving.engine import Engine, ServeConfig, StepResult, energy_report
from repro.serving.params import RequestOutput, SamplingParams
from repro.serving.prefix_cache import PrefixCache
from repro.serving.scheduler import (
    Request,
    Scheduler,
    SchedulerConfig,
    StaticBatchScheduler,
    StepClock,
    run_closed_loop,
    run_open_loop,
    synth_shared_prefix_traffic,
    synth_traffic,
)
from repro.serving.speculative import SpecConfig, SpecDecoder

__all__ = [
    "Engine", "ServeConfig", "StepResult", "energy_report", "PrefixCache",
    "RequestOutput", "SamplingParams", "SpecConfig", "SpecDecoder",
    "Request", "Scheduler", "SchedulerConfig", "StaticBatchScheduler",
    "StepClock", "run_open_loop", "run_closed_loop", "synth_traffic",
    "synth_shared_prefix_traffic",
]
