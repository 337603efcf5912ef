"""Operations and bytes from shapes, and the chip's peaks.

Conventions (the yardstick; a faster program moves the reading, not these):

* A GR-MAC call ``(M, K) @ (K, N)`` does ``2 * M * K * N`` operations,
  counted at the chip's int8 peak: both operands sit on grids of at most
  8 bits. M is the call's own row count, before any padding.
* The least bytes it must move: the weights at ``fmt_w`` bits, the inputs
  at ``fmt_x`` bits and the outputs in the model's dtype.
* Its roofline time is the larger of operations over the int8 peak and
  bytes over the HBM bandwidth; its roofline share is that time over the
  measured time, so no implementation can read above 100%.
* Model FLOPs of a token: twice the weights of every projection and of
  the head, plus attention's score and value products against the keys the
  token sees. Work that a program repeats or pads does not count. Shares of the bf16 peak
  use these.
"""
from __future__ import annotations

import json
import re
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table has {sorted(table)}")
    return table[device_kind]


def fmt_bits(name: str) -> int:
    """Bits of a format name such as ``FP6_E3M2``."""
    return int(re.match(r"FP(\d+)_", name).group(1))


def grmac_ops(m: int, k: int, n: int) -> float:
    return 2.0 * m * k * n


def grmac_bytes(m: int, k: int, n: int, spec: dict) -> float:
    cim = spec["cim"]
    out = 2 if spec["arch"]["dtype"] == "bfloat16" else 4
    return (k * n * fmt_bits(cim["fmt_w"]) / 8
            + m * k * fmt_bits(cim["fmt_x"]) / 8 + m * n * out)


def grmac_roofline_s(m: int, k: int, n: int, spec: dict, pk: dict) -> float:
    return max(grmac_ops(m, k, n) / pk["int8_ops_per_s"],
               grmac_bytes(m, k, n, spec) / pk["hbm_bytes_per_s"])


def projection_weights(arch: dict) -> int:
    """Weights of every projection and the head (one token's matmuls)."""
    d, v, n_l = arch["d_model"], arch["vocab_size"], arch["n_layers"]
    _dense_only(arch)
    h, kv, dh, f = (arch["n_heads"], arch["n_kv_heads"], arch["d_head"],
                    arch["d_ff"])
    layer = d * (h + 2 * kv) * dh + h * dh * d + 3 * d * f
    return n_l * layer + d * v


def token_flops(arch: dict, position: int) -> float:
    """Model FLOPs of one token at ``position`` (0-based) of its sequence,
    forward only."""
    return (2.0 * projection_weights(arch) + 4.0 * arch["n_layers"]
            * arch["n_heads"] * arch["d_head"] * (position + 1))


def span_flops(arch: dict, start: int, count: int) -> float:
    """Model FLOPs of ``count`` tokens at positions start .. start+count-1."""
    if count <= 0:
        return 0.0
    per_pos = 4.0 * arch["n_layers"] * arch["n_heads"] * arch["d_head"]
    return (token_flops(arch, 0) * count
            + per_pos * (count * start + count * (count - 1) / 2))


def call_widths(arch: dict) -> list:
    """Output widths of the configuration's projections and head."""
    _dense_only(arch)
    d, v = arch["d_model"], arch["vocab_size"]
    return [arch["n_heads"] * arch["d_head"],
            arch["n_kv_heads"] * arch["d_head"], d, arch["d_ff"], v]


def _dense_only(arch: dict) -> None:
    if tuple(arch["block_pattern"]) != ("attn",):
        raise ValueError(f"counts only dense attention layers, not "
                         f"{arch['block_pattern']}")
