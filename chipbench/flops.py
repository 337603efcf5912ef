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
  the head, plus what the configuration's reference module counts beyond
  them (``mixer_flops``: attention's score and value products against the
  keys the token sees, a state space's update and readout). Work that a
  program repeats or pads does not count. Shares of the bf16 peak use
  these.

Everything that depends on the architecture (the projections' weights and
widths, the mixer's FLOPs) comes from the module that the configuration
names under "reference" (``chipbench.reference``); what is here holds for
every GR-MAC call.
"""
from __future__ import annotations

import json
import re
from pathlib import Path

from chipbench import reference

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


def projection_weights(spec: dict) -> int:
    """Weights of every projection and the head (one token's matmuls)."""
    return reference.module(spec).projection_weights(spec["arch"])


def token_flops(spec: dict, position: int) -> float:
    """Model FLOPs of one token at ``position`` (0-based) of its sequence,
    forward only."""
    return span_flops(spec, position, 1)


def span_flops(spec: dict, start: int, count: int) -> float:
    """Model FLOPs of ``count`` tokens at positions start .. start+count-1."""
    if count <= 0:
        return 0.0
    mod = reference.module(spec)
    return (2.0 * mod.projection_weights(spec["arch"]) * count
            + mod.mixer_flops(spec["arch"], start, count))


def call_widths(spec: dict) -> list:
    """Output widths of the configuration's projections and head."""
    return reference.module(spec).call_widths(spec["arch"])
