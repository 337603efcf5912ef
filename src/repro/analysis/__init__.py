"""Static-analysis subsystem: machine-checked guarantees for the ledger.

Design note
-----------
Everything downstream of PR 4/5 — the CostLedger, the per-site Pareto
DSE, the deployment fronts — derives energy from shape-only traces that
*trust* model authors to route every projection through ``cim_matmul``
with a valid site label. This package converts that convention into
three machine-checked proofs, one module per pass:

``jaxpr_audit``  (run per config × {prefill, decode, train})
    Walks the closed jaxpr of the exact functions the ledger traces
    (``core.costs.phase_trace_spec``) and proves every ``dot_general`` /
    ``conv`` primitive is attributable: tagged with a
    ``cim_<site>_m<M>_k<K>_n<N>`` marker whose non-transpose
    ``cim_values`` count matches the CostLedger entry exactly, or
    declared digital via a ``dig_*`` scope. Reports untagged MACs with
    source locations and count mismatches.

``invariants``
    ``InstrumentedEngine`` wraps the serving engine's dedicated seams
    (``_compiled_decode``/``_compiled_prefill``/``_fetch``) and enforces
    at most one compile per (arch, bucket)/(arch, sample) executable and
    exactly one device→host transfer per decode step.

``sanitize``
    The opt-in (``REPRO_SANITIZE=1``) numerics sanitizer sink: the
    xla/tiled/ref GR-MAC backends stage in-graph NaN/Inf, pre-ADC
    overflow, and gain-range-limit checks that report per call site via
    ``jax.debug.callback``; structurally zero-cost when unset.

Report schema (``python -m repro.analysis --out ...``)::

    {"schema": 1, "phases": [...], "failures": N,
     "configs": {"<name>": {"failures": N, "phases": {"<phase>": {
         dot_generals, convs, tagged_values, tagged_gains,
         declared_digital, transposes, untagged, untagged_details[],
         ledger_mismatches, ledger_mismatch_details[], dtype_f32,
         dtype_bf16, calls, macs,
         contracts: {"<site>_m<M>_k<K>_n<N>": {ledger, traced}}}}}},
     "invariants": {"violations": N, "configs": {"<name>": {
         traces{}, compiles, fetches, steps}}}}

Run locally::

    PYTHONPATH=src python -m repro.analysis                  # paper config
    PYTHONPATH=src python -m repro.analysis --all-configs \
        --out experiments/audit/audit_report.json            # the CI lane

The committed golden lives at ``experiments/audit/audit_report.json``
and is gated by exact-equality diff in ``benchmarks/compare.py``
(``--bench audit``), so any change in ledger coverage shows up as a
diff, not a silent drift.

Imports are lazy (``__getattr__``): the kernels import ``sanitize``
from inside traced bodies, and an eager package import would cycle
through models → kernels → analysis.
"""
from __future__ import annotations

import importlib

__all__ = ["jaxpr_audit", "invariants", "sanitize", "cli"]


def __getattr__(name):
    if name in __all__:
        return importlib.import_module(f"repro.analysis.{name}")
    raise AttributeError(f"module 'repro.analysis' has no attribute {name!r}")
