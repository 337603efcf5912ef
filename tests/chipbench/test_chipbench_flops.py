"""Operation and byte counts against hand counts, and roofline shares
that cannot pass 100%."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from chipbench import flops

CONFIGS = Path(__file__).resolve().parents[2] / "chipbench" / "configs"


def spec(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_qwen2_token_flops_by_hand():
    a = spec("qwen2-1.5b-grmac")["arch"]
    # per layer: q 1536x1536, k and v 1536x256, o 1536x1536, MLP 3 x
    # 1536x8960; 28 layers; tied head 1536 x 151936
    layer = 1536 * 1536 * 2 + 1536 * 256 * 2 + 3 * 1536 * 8960
    weights = 28 * layer + 1536 * 151936
    assert flops.projection_weights(a) == weights == 1_543_569_408
    # position 99 sees 100 keys: QK and PV, 12 heads x 128, 28 layers
    attn = 4 * 28 * 12 * 128 * 100
    assert flops.token_flops(a, 99) == 2 * weights + attn


def test_layers_other_than_dense_attention_are_refused():
    a = dict(spec("qwen2-1.5b-grmac")["arch"], block_pattern=["ssm"])
    with pytest.raises(ValueError, match="dense attention"):
        flops.projection_weights(a)
    with pytest.raises(ValueError, match="dense attention"):
        flops.call_widths(a)


def test_span_flops_sums_positions():
    a = spec("qwen2-1.5b-grmac")["arch"]
    want = sum(flops.token_flops(a, p) for p in range(40, 57))
    assert flops.span_flops(a, 40, 17) == pytest.approx(want, rel=1e-12)
    assert flops.span_flops(a, 3, 0) == 0.0


def test_grmac_counts_by_hand():
    s = spec("qwen2-1.5b-grmac")
    m, k, n = 8, 1536, 8960
    assert flops.grmac_ops(m, k, n) == 2 * 8 * 1536 * 8960
    # FP4 weights, FP6 inputs, bf16 outputs
    assert flops.grmac_bytes(m, k, n, s) == (
        1536 * 8960 * 4 / 8 + 8 * 1536 * 6 / 8 + 8 * 8960 * 2)


@pytest.mark.parametrize("m", [1, 8, 100, 128, 1024, 2048])
@pytest.mark.parametrize("k,n", [(1536, 8960), (8960, 1536),
                                 (1536, 151936), (4096, 2048)])
def test_roofline_share_never_above_one(m, k, n):
    """A call can take no less than its roofline time, padded or not: a
    kernel that pads M to 128 does more work in more time, and the share
    counts the call's own M."""
    s = spec("qwen2-1.5b-grmac")
    pk = flops.peaks("TPU v5 lite")
    t_min = flops.grmac_roofline_s(m, k, n, s, pk)
    padded = max(m, 128)
    assert flops.grmac_roofline_s(padded, k, n, s, pk) >= t_min
    assert t_min / t_min == 1.0
    ops_bound = flops.grmac_ops(m, k, n) / pk["int8_ops_per_s"]
    bytes_bound = flops.grmac_bytes(m, k, n, s) / pk["hbm_bytes_per_s"]
    assert t_min == max(ops_bound, bytes_bound)


def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("TPU v9 imaginary")
