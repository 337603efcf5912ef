"""GR-MAC kernel subsystem: one op, many backends, shape-aware planning.

The paper's core artifact — the gain-ranged MAC matmul — is exposed as a
single dispatch surface with interchangeable, cross-validated execution
backends:

    ops.cim_matmul        model-facing op (pre-scale, mode switch, STE
                          gradients); what ``models.layers`` calls
    dispatch.grmac_matmul plan-based backend selection + shape padding
    xla.py                fully-vectorized batched-einsum backend — fastest
                          at small M (decode shapes) on CPU/GPU
    tiled.py              fused M(xN)-tiled backend (``lax.scan`` tiles,
                          den/ADC/renorm epilogue inside the tile body) —
                          the large-M (training-shape) winner off-TPU
    grmac_matmul.py       Pallas TPU kernel (default on TPU); its
                          interpret mode is kept as an explicit debug
                          backend ("pallas_interpret")
    ref.py                readable pure-jnp oracle ("ref")

Site threading & cost accounting
--------------------------------
``cim_matmul`` takes a ``site=`` label naming the model call site that
issued the matmul (``core.cim_config.SITES``: ``attn_qkv`` / ``attn_o`` /
``mlp`` / ``moe_router`` / ``moe_expert`` / ``rglru`` / ``ssm`` /
``head``). The site does two jobs at this single choke point:

1. **policy** — ``CIMConfig.for_site(site)`` resolves which design (or
   "off") runs there: ``site_overrides`` first (first-class mixed
   deployments, e.g. a conv-granularity head next to a gr-row FFN), the
   legacy family-level ``apply_to`` otherwise;
2. **accounting** — when a ``core.costs.recording`` trace is active, the
   executed contract ``(site, M, K, N, granularity, fmt_x, fmt_w, n_r)``
   is recorded into the active ``CostLedger`` *before* dispatch, shapes
   read at Python level so a shape-only ``jax.eval_shape`` of the real
   model functions yields exact op counts (``core.costs.trace_decode`` /
   ``trace_prefill`` / ``trace_train``). Outside a trace the hook is one
   list check. ``serving.engine.energy_report`` prices those ledgers per
   site design — there is no separate analytic MAC census to drift.

Two call sites record logical rather than physical shapes: the MoE expert
stacks (``tokens × top_k`` routed rows, not the fixed-capacity dispatch
buffer — recorded explicitly in ``models.moe``) and the LM head (true
``vocab_size``, not the 256-padded matmul width, via ``logical_n=``).

Machine-checked invariants
--------------------------
The accounting contract above is proven, not trusted, by the static
analysis lane (``repro.analysis``, CI ``audit`` job):

1. **Ledger completeness** — every ``cim_matmul`` call wraps its
   realizing contraction in ``jax.named_scope`` markers
   (``ops.site_marker``: ``cim_<site>_m<M>_k<K>_n<N>`` around the call,
   ``cim_values`` on the values contraction, ``cim_gains`` on the unit
   denominator), and model-level digital contractions declare themselves
   (``dig_attn`` / ``dig_ssm_ssd`` / ``dig_ste_bwd``). The jaxpr audit
   walks the traced prefill/decode/train programs and fails on any
   ``dot_general``/``conv`` carrying none of these, and on any marker
   whose traced count disagrees with the CostLedger entry. When adding a
   contraction, either route it through ``cim_matmul`` or wrap it in a
   ``dig_*`` scope — an unlabeled one fails CI with its source location.
2. **Numerics sanitizer** — ``REPRO_SANITIZE=1`` (read per call in
   ``dispatch._run_plan``) makes the xla/tiled/ref backends stage
   in-graph NaN/Inf, pre-ADC overflow (|v| > 1), and gain-range-limit
   checks via ``jax.debug.callback`` into
   ``repro.analysis.sanitize.VIOLATIONS``; unset, the checks are
   structurally absent (zero extra jaxpr primitives, bit-identical
   outputs — asserted by tests/test_sanitize.py). Pallas backends are
   not instrumented (the kernel body is opaque to ``debug.callback``);
   cross-backend 0-ulp equality covers them indirectly.

Backend selection
-----------------
``CIMConfig.backend`` (or a ``backend=`` call override) names a backend or
"auto". "auto" resolves through ``dispatch.plan_for``: pallas on TPU;
off-TPU a ``Plan(backend, tile_m, tile_n)`` keyed on
``(M, K, N, granularity, formats, n_r, platform)`` — served from the
in-memory plan table, then the persisted JSON plan cache, then (with
autotuning enabled) a measure-once micro-probe, else the static heuristic
(``M >= 64`` -> tiled, smaller -> xla). ``CIMConfig.tile_m``/``tile_n``
(and ``grmac_matmul(tile_m=, tile_n=)``) pin tile sizes explicitly;
``ServeConfig.cim_backend``/``TrainConfig.cim_backend`` (+ their
``cim_tile_m``/``cim_tile_n``) override per call site.

Environment knobs
-----------------
``REPRO_GRMAC_BACKEND``      force a backend name for every "auto" call
                             (explicit ``backend=`` arguments still win).
``REPRO_GRMAC_AUTOTUNE=1``   enable the micro-autotune: unknown shapes are
                             probed once (candidate backends x tile sizes,
                             on synthetic operands), the winner is
                             persisted, and later calls — in this or any
                             other process — reuse it for free.
``REPRO_GRMAC_PLAN_CACHE``   path of the persisted plan JSON (default
                             ``~/.cache/repro/grmac_plans.json``).
``REPRO_SANITIZE=1``         stage the in-graph numerics sanitizer on the
                             xla/tiled/ref backends (see "Machine-checked
                             invariants" above); off by default and
                             structurally free when off.

All backends implement the same semantics contract and are cross-checked
at 0-ulp tolerance in tests/test_kernels.py and tests/test_properties.py;
``benchmarks/kernel_bench.py --backend all`` compares their wall time and
``benchmarks/compare.py`` guards the committed numbers against regression.
"""
from repro.kernels.dispatch import (
    BACKENDS,
    Plan,
    grmac_matmul,
    plan_for,
    resolve_backend,
)
from repro.kernels.ops import cim_matmul

__all__ = ["BACKENDS", "Plan", "cim_matmul", "grmac_matmul", "plan_for",
           "resolve_backend"]
