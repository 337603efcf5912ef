"""From the profiler's trace of a window to the numbers that the per-layer
readers take.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler.trace``
wrote and keeps, for each chip the cell uses, the program runs ("XLA
Modules") and the operations ("XLA Ops"), and the harness's host spans.
On the TPU an operation's name is its HLO text and carries no scope
metadata, so the named scopes of the program (``cim_*``, ``dig_*``) are
not visible here. ``RunData`` matches the program runs in order with the dispatches
that the window logged, so that every run of a prefill or decode program
is known with its lanes, lengths and positions.

The normalised form (``to_json``/``from_json``) is what the tests check
the reduction on, with a small trace recorded on the chip.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import re
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from chipbench import flops

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


@dataclasses.dataclass
class Op:
    name: str           # the op's HLO text, compacted (``compact``)
    start: int          # ns, on the trace's clock
    dur: int            # ns


@dataclasses.dataclass
class Device:
    modules: List[tuple]        # (name, start ns, dur ns), by start
    ops: List[Op]               # by start
    host: List[tuple] = dataclasses.field(default_factory=list)
    # the harness's host spans (name, start ns, dur ns), on the same clock


HOST_SPAN_PREFIX = "chipbench."


def load_xplane(trace_dir: str, chips: int) -> List[Device]:
    """The first ``chips`` TPU devices of the newest trace under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    devices = {}
    host = []
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.name, int(ev.start_ns), int(ev.duration_ns))
                            for ev in line.events
                            if ev.name.startswith(HOST_SPAN_PREFIX))
        if not name.startswith("/device:TPU:"):
            continue
        tail = name[len("/device:TPU:"):]
        if not tail.isdigit() or int(tail) >= chips:
            continue
        modules, ops = [], []
        for line in plane.lines:
            if line.name == MODULE_LINE:
                modules = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                           for ev in line.events]
            elif line.name == OP_LINE:
                ops = [Op(compact(ev.name), int(ev.start_ns),
                          int(ev.duration_ns)) for ev in line.events]
        modules.sort(key=lambda m: m[1])
        ops.sort(key=lambda o: o.start)
        devices[int(tail)] = Device(modules, ops)
    host.sort(key=lambda h: h[1])
    for d in devices.values():
        d.host = host
    return [devices[i] for i in sorted(devices)]


_KIND = re.compile(r" ([a-z][\w\-]*)\(")


def compact(hlo: str) -> str:
    """An op's name on the TPU is its HLO text; keep ``%name = kind(``,
    and a kernel's operands too (their shapes are its K and N)."""
    if "tpu_custom_call" in hlo or " = " not in hlo:
        return hlo
    head, rest = hlo.split(" = ", 1)
    m = _KIND.search(" " + rest if rest.startswith(("(", "%")) else rest)
    return f"{head} = {m.group(1)}(" if m else head


def busy_ns(ops: List[Op]) -> int:
    """Length of the union of the operations' intervals."""
    total, end = 0, None
    for o in ops:
        s, e = o.start, o.start + o.dur
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def to_json(devices: List[Device]) -> dict:
    return {"devices": [{"modules": d.modules,
                         "ops": [dataclasses.astuple(o) for o in d.ops],
                         "host": d.host}
                        for d in devices]}


def from_json(data: dict) -> List[Device]:
    return [Device([tuple(m) for m in d["modules"]],
                   [Op(*o) for o in d["ops"]],
                   [tuple(h) for h in d.get("host", [])])
            for d in data["devices"]]


def is_kernel(op: Op) -> bool:
    """A Pallas (Mosaic) kernel launch: on the TPU an op's name is its HLO
    text, and a kernel is a custom call to ``tpu_custom_call``."""
    return "tpu_custom_call" in op.name


def is_container(op: Op) -> bool:
    """A loop or call whose body's ops the trace lists too."""
    return any(f" {kind}(" in op.name for kind in ("while", "conditional",
                                                     "call"))


_OPERAND = re.compile(r"\[(\d+),(\d+)\]")


def kernel_kn(op: Op):
    """(K, N) of a GR-MAC kernel launch, from its operands' shapes
    ``[M, K]`` and ``[K, N]`` (both padded to the kernel's blocks)."""
    args = op.name.split("custom-call(", 1)[-1]
    dims = _OPERAND.findall(args)
    if len(dims) < 2:
        return None
    return int(dims[0][1]), int(dims[1][1])


class RunData:
    """What the per-layer readers read (see ``metrics/``)."""

    def __init__(self, devices: List[Device], *, window_s: float,
                 programs: Dict[str, List[str]], events: list, spec: dict,
                 peaks: dict, chips: int, tracks=()):
        self.devices = devices
        self.window_s = window_s
        self.programs = programs
        self.events = events
        self.spec = spec
        self.arch = spec["arch"]
        self.peaks = peaks
        self.chips = chips
        self.tracks = list(tracks)
        busy = [busy_ns(d.ops) for d in devices]
        self.busy_s = sum(busy) / len(busy) / 1e9 if busy else 0.0
        self.breakdown = self._breakdown()

    # --- program runs matched with the logged dispatches -----------------
    def _kind_of(self, module_name: str) -> Optional[str]:
        base = module_name.split("(", 1)[0]
        for kind, names in self.programs.items():
            if base in names:
                return kind
        return None

    def program_runs(self, kind: str) -> Optional[List[int]]:
        """Device ns of every run of ``kind``'s programs on chip 0, or None
        where their number differs from the dispatches logged."""
        runs = [m[2] for m in self.devices[0].modules
                if self._kind_of(m[0]) == kind]
        logged = sum(1 for e in self.events if e[0] == kind)
        return runs if runs and len(runs) == logged else None

    def prefill_tokens(self) -> int:
        return int(sum(int(e[3].sum()) for e in self.events
                       if e[0] == "prefill"))

    def prefill_flops(self) -> float:
        total = 0.0
        for e in self.events:
            if e[0] == "prefill":
                for start, n in zip(e[2], e[3]):
                    total += flops.span_flops(self.arch, int(start), int(n))
        return total

    def decode_flops(self) -> float:
        total = 0.0
        for e in self.events:
            if e[0] == "decode":
                for pos, on in zip(e[2], e[3]):
                    if on:
                        total += flops.token_flops(self.arch, int(pos))
        return total

    # --- kernels and the CIM layer --------------------------------------
    def kernel_calls(self) -> List[tuple]:
        """((M, K, N), device ns) of every GR-MAC kernel launch on chip 0
        inside a prefill or decode run. M is the rows of the run's dispatch
        (every projection of these models sees all of them); K and N come
        from the launch's operands, N unpadded to the configuration's
        projection widths (``flops.call_widths``)."""
        runs = {k: [m for m in self.devices[0].modules
                    if self._kind_of(m[0]) == k] for k in self.programs}
        rows = {k: [int(np.prod(e[1].shape)) for e in self.events
                    if e[0] == k] for k in self.programs}
        widths = flops.call_widths(self.arch)
        spans = []
        for k in runs:
            if len(runs[k]) != len(rows[k]):
                return []
            spans += [(m[1], m[1] + m[2], r) for m, r in zip(runs[k], rows[k])]
        spans.sort()
        out, j = [], 0
        for o in self.devices[0].ops:
            if not is_kernel(o):
                continue
            while j < len(spans) and spans[j][1] < o.start:
                j += 1
            if j == len(spans) or o.start < spans[j][0]:
                continue
            kn = kernel_kn(o)
            if kn is None:
                continue
            k, n_pad = kn
            # padding adds less than 256 columns (the vocabulary's 256,
            # the kernel's 128-wide blocks)
            n = max((w for w in widths if 0 <= n_pad - w < 256),
                    default=n_pad)
            out.append(((spans[j][2], k, n), o.dur))
        return out

    # --- where the time goes ---------------------------------------------
    def _breakdown(self) -> dict:
        """The device ops that took most time, and the device's idle time
        between ops by what the host was doing then: the harness's host
        span around the gap's middle, else the programs on either side."""
        if not self.devices:
            return {"device_ops": [], "idle_gaps": []}
        d = self.devices[0]
        by_op: Dict[str, int] = {}
        for o in d.ops:
            if is_container(o):
                continue
            key = _op_label(o)
            by_op[key] = by_op.get(key, 0) + o.dur
        idle: Dict[str, int] = {}
        end = None
        for o in d.ops:
            if end is not None and o.start > end:
                key = self._gap_label(end, o.start)
                idle[key] = idle.get(key, 0) + (o.start - end)
            end = o.start + o.dur if end is None else max(end,
                                                          o.start + o.dur)
        return {"device_ops": _top(by_op), "idle_gaps": _top(idle)}

    def _gap_label(self, start: int, stop: int) -> str:
        mid = (start + stop) // 2
        d = self.devices[0]
        spans = [h for h in d.host if h[1] <= mid <= h[1] + h[2]]
        if spans:
            return "host " + spans[-1][0][len(HOST_SPAN_PREFIX):]
        inside = [m for m in d.modules if m[1] <= mid <= m[1] + m[2]]
        if inside:
            return "inside " + _short(inside[-1][0])
        return "between programs"


def _top(totals: Dict[str, int]) -> list:
    return [[k, v / 1e9] for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])[:10]]


def _short(module_name: str) -> str:
    return module_name.split("(", 1)[0]


def _op_label(o: Op) -> str:
    """An op's kind for the breakdown: the kernel, or the HLO op's name
    without its number (``%negate_select_fusion.19`` ->
    ``negate_select_fusion``)."""
    if is_kernel(o):
        return "grmac_kernel"
    head = o.name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def reduce(ctx) -> RunData:
    """The traced window of a run."""
    from chipbench.reference import replay
    devices = load_xplane(str(ctx.trace_dir), ctx.entry["chips"])
    t0, t1 = ctx.window
    return RunData(devices, window_s=t1 - t0, programs=ctx.modules,
                   events=[e for e in replay.host_events(ctx.log_events)
                           if e[0] in ("prefill", "decode")],
                   spec=ctx.spec, peaks=flops.peaks(ctx.device["kind"]),
                   chips=ctx.entry["chips"], tracks=ctx.tracks)


def save_excerpt(devices: List[Device], path: Path, max_modules: int) -> None:
    """A small normalised excerpt of a trace, gzipped: the first
    ``max_modules`` program runs of chip 0 with the operations and host
    spans inside them."""
    d = devices[0]
    mods = d.modules[:max_modules]
    start, end = mods[0][1], mods[-1][1] + mods[-1][2]
    ops = [o for o in d.ops if start <= o.start <= end]
    host = [h for h in d.host if h[1] <= end and h[1] + h[2] >= start]
    Path(path).write_bytes(gzip.compress(json.dumps(
        to_json([Device(mods, ops, host)]), separators=(",", ":")).encode()))
