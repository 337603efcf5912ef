"""Public CIM matmul ops used by the model layers.

``cim_matmul(x, w, cfg, site=...)`` is a drop-in einsum-style matmul over
the last dim of ``x``:   (..., K) @ (K, N) -> (..., N).

Pipeline
--------
0. per-site policy: ``cfg.for_site(site)`` resolves which design (or
   "off") runs at this call site (``CIMConfig.site_overrides`` first,
   the legacy ``apply_to`` families otherwise), and the contract
   ``(site, M, K, N, design)`` is recorded into the active
   ``core.costs.CostLedger`` when a trace is running — this is the single
   choke point that keeps energy accounting structurally tied to the
   models (see core/costs.py).
1. dynamic pre-scale: activations are normalized into [-1, 1] by their
   per-tensor absmax (the CIM full-scale reference); weights likewise.
2. mode dispatch:
     off        exact matmul (digital baseline)
     fakequant  format-grid quantization of x and w, exact accumulation
     grmac      full GR-MAC block simulation, executed by the backend
                planned through ``kernels.dispatch`` (``cfg.backend`` or
                the ``backend=`` override; "auto" plans per shape — small-M
                decode hits the batched-einsum XLA path, large-M training
                shapes the fused tiled path, TPU the Pallas kernel;
                ``cfg.tile_m``/``cfg.tile_n`` pin the tile sizes)
3. straight-through gradients: the backward pass applies the exact-matmul
   VJP to the *raw* (unquantized, unscaled) saved operands — the standard
   STE estimator — so the op is trainable. (The backward is therefore
   digital by construction; only forward contracts hit the analog array
   and the ledger.)

``logical_n`` overrides the N recorded into the ledger (the LM head
records the true ``vocab_size``, not the 256-aligned ``padded_vocab`` —
pad columns are masked and would never be mapped onto an array); the
matmul itself always runs at the physical shapes.

All GR-MAC backends implement the same contract and are cross-validated in
tests/test_kernels.py.

Audit markers
-------------
Every call is wrapped in a ``jax.named_scope`` marker
``cim_<site>_m<M>_k<K>_n<N>`` carrying the *ledger* contract (logical N for
the LM head), and the contraction that realizes it carries a nested
``cim_values`` scope (``cim_gains`` for the unit-normalization denominator,
``dig_ste_bwd`` for the digital STE backward). The scopes are metadata-only
(they change no jaxpr primitive and no numerics); the jaxpr ledger audit
(``repro.analysis.jaxpr_audit``) walks traced model functions and proves
every ``dot_general`` is attributable to one of these markers — or to an
explicitly declared digital ``dig_*`` scope — with call counts matching the
``CostLedger`` exactly.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import costs
from repro.core.cim_config import CIMConfig
from repro.core.formats import IntFormat, quantize, quantize_any

from .dispatch import grmac_matmul, resolve_backend

__all__ = ["cim_matmul", "site_marker"]

_EPS = 1e-12


def site_marker(site: Optional[str], m: int, k: int, n: int) -> str:
    """The audit marker naming one ledger contract: parsed back by
    ``repro.analysis.jaxpr_audit`` (site names contain underscores, so the
    ``_m<digits>`` suffix anchors the parse)."""
    return f"cim_{site or 'unsited'}_m{m}_k{k}_n{n}"


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _cim_matmul_2d(x, w, cfg: CIMConfig, backend: str, site: str):
    """(M, K) @ (K, N) with CIM numerics and STE gradients. ``site`` is
    metadata only (sanitizer context tag, and the name of the Pallas
    launch in a TPU trace); it never changes numerics."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    sx = jnp.maximum(jnp.max(jnp.abs(x32)), _EPS)
    sw = jnp.maximum(jnp.max(jnp.abs(w32)), _EPS)
    xn = x32 / sx
    wn = w32 / sw
    if cfg.mode == "fakequant":
        # fmt_x may be an IntFormat (the DSE sweeps the INT ladder and
        # per-site overrides can carry its choices); fmt_w is always FP
        with jax.named_scope("cim_values"):
            out = quantize_any(xn, cfg.fmt_x) @ quantize(wn, cfg.fmt_w)
    elif cfg.mode == "grmac":
        if isinstance(cfg.fmt_x, IntFormat):
            raise NotImplementedError(
                "grmac execution with an IntFormat input is not "
                "implemented (the gr_int signal chain is priced "
                "analytically by core.costs/core.dse but has no kernel "
                "backend): deploy INT per-site designs with "
                "mode='fakequant', or pick an FP format")
        out = grmac_matmul(
            xn,
            quantize(wn, cfg.fmt_w),
            fmt_x=cfg.fmt_x,
            fmt_w=cfg.fmt_w,
            n_r=cfg.n_r,
            enob=cfg.resolved_enob(),
            granularity=cfg.granularity,
            backend=backend,
            tile_m=cfg.tile_m,
            tile_n=cfg.tile_n,
            tag=site,
        )
    else:  # off
        with jax.named_scope("cim_values"):
            out = xn @ wn
    return (out * (sx * sw)).astype(dtype)


def _fwd(x, w, cfg, backend, site):
    out = _cim_matmul_2d(x, w, cfg, backend, site)
    return out, (x, w)


def _bwd(cfg, backend, site, res, g):
    x, w = res
    # Straight-through: gradients flow as if the matmul were exact. The
    # dig_ste_bwd scope declares these contractions digital-by-design to
    # the jaxpr ledger audit (the backward never hits the analog array).
    with jax.named_scope("dig_ste_bwd"):
        gx = (g @ w.T.astype(g.dtype)).astype(x.dtype)
        gw = (x.T.astype(g.dtype) @ g).astype(w.dtype)
    return gx, gw


_cim_matmul_2d.defvjp(_fwd, _bwd)


def cim_matmul(
    x: jax.Array,
    w: jax.Array,
    cfg: Optional[CIMConfig] = None,
    *,
    site: Optional[str] = None,
    backend: Optional[str] = None,
    use_kernel: Optional[bool] = None,
    logical_n: Optional[int] = None,
) -> jax.Array:
    """(..., K) @ (K, N) with CIM numerics per ``cfg.for_site(site)``
    (None/off = exact digital matmul).

    ``site`` names the model call site (see ``core.cim_config.SITES``);
    ``site=None`` treats ``cfg`` as already resolved (external callers).
    Backend precedence: ``backend=`` argument > ``cfg.backend`` > platform
    auto-selection (see ``kernels.dispatch``). ``use_kernel`` is the legacy
    boolean knob: True forces the Pallas kernel, False the fast XLA path.
    """
    eff = cfg.for_site(site) if cfg is not None else None
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w.shape[-1]
    m = math.prod(lead)
    ledger_n = n if logical_n is None else logical_n
    costs.record_matmul(site, m, k, ledger_n, eff)
    marker = site_marker(site, m, k, ledger_n)
    if eff is None or not eff.enabled:
        with jax.named_scope(marker), jax.named_scope("cim_values"):
            return x @ w
    if backend is None:
        if use_kernel is not None:
            backend = "pallas" if use_kernel else "xla"
        else:
            backend = eff.backend
    # resolve outside the custom_vjp so the nondiff arg is a concrete,
    # hashable backend name (stable jit cache key)
    backend = resolve_backend(backend)
    with jax.named_scope(marker):
        out = _cim_matmul_2d(x.reshape(-1, k), w, eff, backend, site or "")
    return out.reshape(*lead, n)
