"""Jaxpr ledger audit: prove the CostLedger covers every MAC.

The energy bound the paper derives is only as good as the MAC accounting
under it — any ``dot_general``/``conv`` that escapes the ledger silently
breaks the bound. This pass traces the *same* functions the ledger traces
(``core.costs.phase_trace_spec``: prefill / decode / train-grad per arch),
walks the closed jaxpr — including ``scan``/``cond``/``pjit``/``custom_vjp``
sub-jaxprs — and classifies every MAC primitive by the ``jax.named_scope``
markers the kernels and models stamp:

``cim_<site>_m<M>_k<K>_n<N>``
    one ``cim_matmul`` ledger contract (``kernels.ops.site_marker``); the
    nested ``cim_values`` scope marks the contraction that realizes it
    (``cim_gains`` the unit-normalization denominator). The audit counts
    non-transpose ``cim_values`` primitives per marker and cross-checks
    the count against the ledger entry exactly.
``dig_*`` (``dig_attn``, ``dig_ssm_ssd``, ``dig_ste_bwd``)
    contractions that are digital *by design* (attention scores, SSD dual
    form, the STE backward) — declared, so their absence from the ledger
    is proven intentional rather than assumed.
anything else
    an **untagged MAC** — a ledger leak, reported with the primitive's
    user source location.

Name-stack semantics (verified on jax 0.9.0): sub-jaxpr bodies reset
``eqn.source_info.name_stack``, but the call eqn carries the enclosing
scopes, so the walker prefix-accumulates stacks when it recurses. Under
``grad``, transposed applications carry ``transpose(...)`` in the stack
and are excluded from forward counts (the ledger records forward
contracts only; the STE backward is explicitly digital).

The audit forces a deterministic kernel regime during tracing
(``REPRO_GRMAC_BACKEND=xla`` so no ``pallas_call`` hides its dots,
sanitizer off).
"""
from __future__ import annotations

import contextlib
import os
import re
from typing import Dict, List, Optional, Tuple

import jax

from repro.core import costs
from repro.core.cim_config import SITES

__all__ = [
    "MARKER_RE",
    "MAC_PRIMITIVES",
    "iter_eqns",
    "audit_phase",
    "audit_arch",
]

# Anchored on "_m<digits>_k<digits>_n<digits>": site names themselves
# contain underscores (attn_qkv, moe_expert) but never that suffix shape.
MARKER_RE = re.compile(r"cim_(?P<site>\w+?)_m(?P<m>\d+)_k(?P<k>\d+)_n(?P<n>\d+)")
DIG_RE = re.compile(r"dig_\w+")

MAC_PRIMITIVES = ("dot_general", "conv_general_dilated")

# Primitives that could swallow MAC primitives where the walker cannot see
# them. The audit regime forces the xla backend so none should appear; if
# one does, it is reported as opaque rather than silently passed.
_OPAQUE = ("pallas_call",)


def _jaxpr_types():
    from jax._src import core as _core
    return _core.Jaxpr, _core.ClosedJaxpr


def _sub_jaxprs(params: dict):
    jaxpr_t, closed_t = _jaxpr_types()
    for v in params.values():
        items = v if isinstance(v, (list, tuple)) else (v,)
        for x in items:
            if isinstance(x, closed_t):
                yield x.jaxpr
            elif isinstance(x, jaxpr_t):
                yield x


def iter_eqns(jaxpr, prefix: str = ""):
    """Yield ``(eqn, effective_name_stack)`` over a jaxpr and all its
    sub-jaxprs. Sub-jaxpr traces reset the name stack, so the effective
    stack prefixes the enclosing call eqns' stacks onto each eqn's own."""
    for eqn in jaxpr.eqns:
        own = str(eqn.source_info.name_stack)
        eff = f"{prefix}/{own}" if prefix and own else (prefix or own)
        yield eqn, eff
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub, eff)


def _source_of(eqn) -> Tuple[Optional[str], Optional[int]]:
    """The innermost user (non-JAX) frame that staged ``eqn``."""
    from jax._src import source_info_util  # no public equivalent
    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is None:
        return None, None
    return frame.file_name, int(frame.start_line)


@contextlib.contextmanager
def _audit_env():
    """Pin the kernel regime for a trace: concrete xla backend (Pallas
    hides its dots inside ``pallas_call``) and sanitizer off (no
    ``debug_callback`` noise in the golden)."""
    keys = {"REPRO_GRMAC_BACKEND": "xla", "REPRO_SANITIZE": "0"}
    saved = {k: os.environ.get(k) for k in keys}
    os.environ.update(keys)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _ledger_contracts(ledger: costs.CostLedger) -> Dict[str, dict]:
    """Collapse ledger entries to marker-keyed contract records."""
    out: Dict[str, dict] = {}
    for entry, count in ledger.entries():
        key = f"{entry.site}_m{entry.m}_k{entry.k}_n{entry.n}"
        rec = out.setdefault(key, {
            "site": entry.site, "ledger": 0,
            "fmt_x": entry.fmt_x.name, "fmt_w": entry.fmt_w.name,
            "mode": entry.mode, "granularity": entry.granularity,
        })
        rec["ledger"] += count
    return out


def audit_phase(arch, phase: str) -> dict:
    """Audit one (arch, phase): trace, walk, classify, cross-check.

    Returns a JSON-able dict; ``untagged == 0`` and
    ``ledger_mismatches == 0`` are the pass conditions.
    """
    fn, args = costs.phase_trace_spec(arch, phase)
    ledger = costs.CostLedger()
    with _audit_env():
        with costs.recording(ledger):
            closed = jax.make_jaxpr(fn)(*args)

    contracts = _ledger_contracts(ledger)
    for rec in contracts.values():
        rec["traced"] = 0

    n_dot = n_conv = 0
    tagged_values = tagged_gains = tagged_other = 0
    declared_digital = transposes = 0
    dtype_f32 = dtype_bf16 = 0
    untagged: List[dict] = []
    unknown_sites: List[dict] = []
    opaque: List[dict] = []

    for eqn, stack in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name in _OPAQUE:
            fname, line = _source_of(eqn)
            opaque.append({"primitive": name, "stack": stack,
                           "file": fname, "line": line})
            continue
        if name not in MAC_PRIMITIVES:
            continue
        if name == "dot_general":
            n_dot += 1
        else:
            n_conv += 1
        if "transpose(" in stack:
            # backward transpose of a forward contraction: the forward
            # instance is what the ledger counts; an untagged transpose
            # implies an untagged forward, already reported there.
            transposes += 1
            continue
        marker = None
        for m in MARKER_RE.finditer(stack):
            marker = m           # innermost (rightmost) marker wins
        if marker is not None:
            key = marker.group(0)[len("cim_"):]
            site = marker.group("site")
            rec = contracts.get(key)
            if site not in SITES or rec is None:
                fname, line = _source_of(eqn)
                unknown_sites.append(
                    {"marker": marker.group(0), "stack": stack,
                     "file": fname, "line": line,
                     "reason": ("site not in SITES" if site not in SITES
                                else "no matching ledger contract")})
                continue
            if "cim_values" in stack:
                tagged_values += 1
                rec["traced"] += 1
                op = eqn.invars[0].aval.dtype
                if str(op) == "bfloat16":
                    dtype_bf16 += 1
                else:
                    dtype_f32 += 1
            elif "cim_gains" in stack:
                tagged_gains += 1
            else:
                # under a site marker but neither values nor gains: still
                # attributable (e.g. helper contractions a future backend
                # adds), counted separately so the golden surfaces them
                tagged_other += 1
            continue
        if DIG_RE.search(stack):
            declared_digital += 1
            continue
        fname, line = _source_of(eqn)
        untagged.append({"primitive": name, "stack": stack,
                         "file": fname, "line": line})

    mismatches = [
        {"contract": key, "ledger": rec["ledger"], "traced": rec["traced"]}
        for key, rec in sorted(contracts.items())
        if rec["ledger"] != rec["traced"]
    ]

    return {
        "phase": phase,
        "dot_generals": n_dot,
        "convs": n_conv,
        "tagged_values": tagged_values,
        "tagged_gains": tagged_gains,
        "tagged_other": tagged_other,
        "declared_digital": declared_digital,
        "transposes": transposes,
        "untagged": len(untagged),
        "untagged_details": untagged,
        "unknown_site_details": unknown_sites,
        "opaque_details": opaque,
        "ledger_mismatches": len(mismatches) + len(unknown_sites)
        + len(opaque),
        "ledger_mismatch_details": mismatches,
        "dtype_f32": dtype_f32,
        "dtype_bf16": dtype_bf16,
        "calls": sum(r["ledger"] for r in contracts.values()),
        "macs": ledger.macs(),
        "contracts": {
            key: {"ledger": rec["ledger"], "traced": rec["traced"]}
            for key, rec in sorted(contracts.items())
        },
    }


def audit_arch(arch, phases=("prefill", "decode", "train")) -> dict:
    """Audit every phase of one arch."""
    out = {"phases": {p: audit_phase(arch, p) for p in phases}}
    out["failures"] = sum(ph["untagged"] + ph["ledger_mismatches"]
                          for ph in out["phases"].values())
    return out
