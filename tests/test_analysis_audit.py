"""Jaxpr ledger audit (``repro.analysis.jaxpr_audit``): real archs audit
clean (every MAC tagged or declared digital, per-contract counts matching
the CostLedger exactly), and one seeded untagged contraction in a model
layer fails the audit with the leak's source location — the property the
whole pass exists to enforce."""
import jax.numpy as jnp

from repro.analysis.jaxpr_audit import MARKER_RE, audit_arch, audit_phase
from repro.configs import get_config
from repro.kernels.ops import site_marker
from repro.models import layers as L


def test_marker_grammar_roundtrip():
    m = MARKER_RE.fullmatch(site_marker("attn_qkv", 4, 896, 1152))
    assert m is not None
    assert m.group("site") == "attn_qkv"
    assert tuple(map(int, (m.group("m"), m.group("k"), m.group("n")))) == \
        (4, 896, 1152)
    # underscored site names must parse whole: the regex anchors the site
    # on the "_m<digits>_k<digits>_n<digits>" suffix, which no site name
    # can contain
    m = MARKER_RE.fullmatch(site_marker("moe_expert", 8, 64, 128))
    assert m is not None and m.group("site") == "moe_expert"


def test_paper_arch_decode_audits_clean():
    arch = get_config("paper-cim-120m").reduced()
    res = audit_phase(arch, "decode")
    assert res["untagged"] == 0, res["untagged_details"]
    assert res["ledger_mismatches"] == 0, res["ledger_mismatch_details"]
    assert res["tagged_values"] > 0
    # the cross-check really binds: every ledger contract was traced
    # exactly as many times as it was recorded
    assert res["contracts"]
    for key, c in res["contracts"].items():
        assert c["ledger"] == c["traced"], (key, c)


def test_train_grad_audits_clean_with_transposes_excluded():
    arch = get_config("qwen2-1.5b").reduced()
    res = audit_arch(arch, ("train",))
    ph = res["phases"]["train"]
    assert res["failures"] == 0, ph
    assert ph["transposes"] > 0         # grad transposes seen, not counted
    assert ph["declared_digital"] > 0   # attention scores + STE backward


def test_seeded_untagged_einsum_fails_audit_with_source(monkeypatch):
    """The acceptance criterion: an untagged contraction smuggled into a
    model layer must fail the audit and name this file as the source."""
    arch = get_config("paper-cim-120m").reduced()
    orig = L.rmsnorm

    def leaky_rmsnorm(p, x, eps=1e-6):
        out = orig(p, x, eps)
        return out @ jnp.eye(out.shape[-1], dtype=out.dtype)  # the leak

    monkeypatch.setattr(L, "rmsnorm", leaky_rmsnorm)
    res = audit_phase(arch, "decode")
    assert res["untagged"] > 0
    leak = res["untagged_details"][0]
    assert leak["primitive"] == "dot_general"
    assert leak["file"] and leak["file"].endswith("test_analysis_audit.py")
    assert isinstance(leak["line"], int) and leak["line"] > 0
    # the leak bypasses cim_matmul, so the ledger cross-check itself stays
    # clean — untagged and mismatch are independent failure axes
    assert res["ledger_mismatches"] == 0, res["ledger_mismatch_details"]
