"""What the program's own names say in a traced window.

``trace.load_xplane`` keeps the device's program runs and ops and the
harness's host spans (``chipbench.*``). This module reads, from the same
``.xplane.pb``, the program's host spans (``repro.*``, see
``repro.serving.spans``) with their arguments, and reads the device ops by
the names the program gives them:

- Program kinds: the serving programs are ``jit_prefill_b<bucket>``,
  ``jit_decode_greedy``/``jit_decode_sample`` and ``jit_verify_b<bucket>``;
  ``serve.program_modules`` reads these names from the programs it warmed
  up, as before.
- Sites: on the TPU an op's name is its HLO text, and device events carry
  no scope metadata, so the program's named scopes (``cim_*``, ``dig_*``)
  never reach the trace. A GR-MAC launch's HLO instruction takes the name
  of the innermost jitted function around its ``pallas_call``, and the
  program launches each model call site's matmuls under ``grmac_<site>``
  (``kernels.grmac_matmul.named_kernel``): ``kernel_site`` reads it back.
- Weight preparation: a launch's HLO text lists its operands; the second,
  its weights, names the op that re-quantised them (``kernel_weight``).
  The weight's absmax scale is a separate op that reduces the same weight
  to a scalar, joined to the re-quantisation through a scalar ``%max``
  that has no device event: ``prep_heads`` finds it by that operand.

The per-layer readers get a ``trace.RunData``, whose host spans are the
harness's only and whose op names keep no operands; ``program_spans`` and
``op_texts`` then read the program's spans and each op's operands from the
run's trace file. A run whose devices already hold ``repro.*`` spans and
ops named with their operands (a recorded excerpt, a hand-made trace) is
read as it is.

    python3 chipbench/program_trace.py --trace-dir <dir> [--excerpt <out>]

prints kernel time by site and device idle time by the innermost host span
around it, and with ``--excerpt`` cuts a small excerpt that keeps the
program's spans and each op's operands (``trace.save_excerpt``).
"""
from __future__ import annotations

import argparse
import bisect
import functools
import glob
import os
import re
import sys
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness, trace  # noqa: E402

PROGRAM_PREFIX = "repro."
SPAN_PREFIXES = (trace.HOST_SPAN_PREFIX, PROGRAM_PREFIX)
# where chipbench/run.py writes a traced run's profile, one directory a cell
TRACE_ROOT = harness.ROOT / ".bench_trace"

STEP = "repro.scheduler.step"


# --- the program's host spans and its ops' operands ----------------------
def newest_xplane(trace_dir) -> Optional[str]:
    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def load_trace(path: str) -> tuple:
    """(host spans, start of chip 0's first program run, op texts) of one
    trace file. A span is (name, start ns, duration ns, {argument: value});
    the harness's and the program's are kept, the Python tracer's calls are
    not. The start is None where the file has no TPU program runs. The op
    texts are {module name: {op's ``%name``: ``short_hlo`` text}}, from the
    first run of each of chip 0's programs."""
    return _load_trace(path, os.path.getmtime(path))


@functools.lru_cache(maxsize=2)
def _load_trace(path: str, mtime: float) -> tuple:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, first, texts = [], None, {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((ev.name, int(ev.start_ns), int(ev.duration_ns),
                              dict(ev.stats))
                             for ev in line.events
                             if ev.name.startswith(SPAN_PREFIXES))
        elif plane.name == "/device:TPU:0":
            lines = {line.name: line for line in plane.lines}
            runs = {}                   # each program's first run
            for ev in (lines[trace.MODULE_LINE].events
                       if trace.MODULE_LINE in lines else ()):
                a = int(ev.start_ns)
                if ev.name not in runs or a < runs[ev.name][0]:
                    runs[ev.name] = (a, a + int(ev.duration_ns))
            first = min((r[0] for r in runs.values()), default=None)
            windows = sorted((a, b, m) for m, (a, b) in runs.items())
            starts = [w[0] for w in windows]
            for ev in (lines[trace.OP_LINE].events
                       if trace.OP_LINE in lines else ()):
                t = int(ev.start_ns)
                i = bisect.bisect_right(starts, t) - 1
                if i >= 0 and t <= windows[i][1]:
                    text = short_hlo(ev.name)
                    texts.setdefault(windows[i][2], {})[
                        text.split(" = ", 1)[0]] = text
    spans.sort(key=lambda s: s[1])
    return spans, first, texts


def _run_trace(run, trace_root) -> tuple:
    """(spans, op texts) of the newest trace under ``trace_root``
    (``TRACE_ROOT``) if it is the run's (its chip 0 starts its first
    program run when the run's does), else ([], {})."""
    path = newest_xplane(TRACE_ROOT if trace_root is None else trace_root)
    if path is None:
        return [], {}
    spans, first, texts = load_trace(path)
    if first != run.devices[0].modules[0][1]:
        return [], {}
    return spans, texts


def program_spans(run, trace_root=None) -> List[tuple]:
    """Every host span of the run, the program's included, by start: the
    run's own where they hold the program's, else those of its trace
    file (``_run_trace``), else none."""
    if not run.devices or not run.devices[0].modules:
        return []
    host = run.devices[0].host
    if any(h[0].startswith(PROGRAM_PREFIX) for h in host):
        return list(host)
    return _run_trace(run, trace_root)[0]


def op_texts(run, trace_root=None) -> Dict[str, Dict[str, str]]:
    """{module name: {op's ``%name``: text listing its operands}} of chip
    0's program runs: from the ops' own names where they list their
    operands, else from the run's trace file (``_run_trace``)."""
    if not run.devices or not run.devices[0].modules:
        return {}
    out: Dict[str, Dict[str, str]] = {}
    for m, _, _, ops in runs_with_ops(run, None):
        for o in ops:
            # a ``trace.compact`` name ends at its kind's parenthesis
            if not trace.is_kernel(o) and not o.name.endswith("("):
                out.setdefault(m, {})[op_head(o)] = o.name
    return out or _run_trace(run, trace_root)[1]


# --- device idle time by host span ----------------------------------------
def idle_gaps(ops: List[trace.Op]) -> List[tuple]:
    """(start, stop) of every interval between the device's ops (ops by
    start, overlapping ones merged), as ``RunData``'s breakdown counts
    them."""
    gaps, end = [], None
    for o in ops:
        if end is not None and o.start > end:
            gaps.append((end, o.start))
        end = o.start + o.dur if end is None else max(end, o.start + o.dur)
    return gaps


class SpanIndex:
    """Finds the innermost span (the last to start) that holds a time."""

    def __init__(self, spans: List[tuple]):
        self.spans = sorted(spans, key=lambda s: s[1])
        self.starts = [s[1] for s in self.spans]
        self.reach = []             # latest end of the spans up to each one
        r = None
        for s in self.spans:
            r = s[1] + s[2] if r is None else max(r, s[1] + s[2])
            self.reach.append(r)

    def innermost(self, t: int) -> Optional[tuple]:
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.reach[i] >= t:
            s = self.spans[i]
            if s[1] + s[2] >= t:
                return s
            i -= 1
        return None


def idle_by_span(ops: List[trace.Op], spans: List[tuple]) -> Dict[str, int]:
    """Device idle ns by the innermost host span around each gap's middle
    (its full name), or ``None`` where the host was in no span."""
    index = SpanIndex(spans)
    out: Dict[str, int] = {}
    for a, b in idle_gaps(ops):
        s = index.innermost((a + b) // 2)
        key = s[0] if s else None
        out[key] = out.get(key, 0) + (b - a)
    return out


def idle_in_steps(ops: List[trace.Op], spans: List[tuple]) -> tuple:
    """(device idle ns whose middle lies in a ``scheduler.step`` span, the
    number of such spans)."""
    steps = SpanIndex([s for s in spans if s[0] == STEP])
    idle = sum(b - a for a, b in idle_gaps(ops)
               if steps.innermost((a + b) // 2) is not None)
    return idle, len(steps.spans)


# --- GR-MAC launches by site, and ops by their operands ---------------------
_SITE = re.compile(r"%grmac_(\w+?)(?:\.\d+)? = ")
_OPERAND = re.compile(r"%[\w.\-]+")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_ATTRIBUTES = re.compile(r"\),\s*[a-z_]+=")
_TYPED = re.compile(r"(\w+\[[\d,]*\]) (%[\w.\-]+)")
_SCALAR = re.compile(r"\w+\[\]")
UNSITED = "grmac_matmul_pallas"


def short_hlo(text: str) -> str:
    """An op's HLO text as the TPU's trace gives it, without layouts or
    attributes: ``%name = type kind(type %operand, ...)``. A launch keeps
    its whole text (``trace.compact`` does too)."""
    if "tpu_custom_call" in text or " = " not in text:
        return text
    s = _LAYOUT.sub("", text)
    m = _ATTRIBUTES.search(s)
    return s[:m.start() + 1] if m else s


def operands(text: Optional[str]) -> List[Tuple[str, str]]:
    """(type, ``%name``) of each operand an op's text lists."""
    if not text or " = " not in text:
        return []
    rest = text.split(" = ", 1)[1]
    m = trace._KIND.search(" " + rest)
    return _TYPED.findall(rest[m.end() - 1:]) if m else []


def makes_scalar(text: Optional[str]) -> bool:
    """Whether an op's text says it makes one scalar (``f32[]``)."""
    if not text or " = " not in text:
        return False
    rest = text.split(" = ", 1)[1]
    m = trace._KIND.search(" " + rest)
    return bool(m) and _SCALAR.fullmatch(rest[:m.start()].strip()) is not None


def kernel_site(op: trace.Op) -> Optional[str]:
    """The model call site a GR-MAC launch was made for (``head``,
    ``attn_qkv``, ...), from its name ``%grmac_<site>.N``; None for a
    launch under the kernel's own name or an op that is no launch."""
    if not trace.is_kernel(op):
        return None
    m = _SITE.match(op.name)
    if m is None or "grmac_" + m.group(1) == UNSITED:
        return None
    return m.group(1)


def kernel_weight(op: trace.Op) -> Optional[str]:
    """The name (``%negate_select_fusion.15``) of the op that made a
    launch's weight operand, its second operand."""
    args = op.name.split("custom-call(", 1)
    if len(args) < 2:
        return None
    names = _OPERAND.findall(args[1].split("custom_call_target", 1)[0])
    return names[1] if len(names) > 1 else None


def op_head(op: trace.Op) -> str:
    return op.name.split(" = ", 1)[0]


def kernel_ns_by_site(ops: List[trace.Op]) -> Dict[Optional[str], int]:
    """Device ns of every GR-MAC launch by site (None: no site)."""
    out: Dict[Optional[str], int] = {}
    for o in ops:
        if trace.is_kernel(o):
            s = kernel_site(o)
            out[s] = out.get(s, 0) + o.dur
    return out


def runs_with_ops(run, kinds) -> List[tuple]:
    """(module name, kind, run ns, ops inside it) of chip 0's runs of
    ``kinds``' programs (every program where ``kinds`` is None), by
    start."""
    if not run.devices:
        return []
    d = run.devices[0]
    runs = [(m, run._kind_of(m[0])) for m in d.modules]
    runs = [(m, k) for m, k in runs if kinds is None or k in kinds]
    starts = [o.start for o in d.ops]
    out = []
    for m, k in runs:
        lo = bisect.bisect_left(starts, m[1])
        hi = bisect.bisect_right(starts, m[1] + m[2])
        out.append((m[0], k, m[2], d.ops[lo:hi]))
    return out


def prep_heads(ops: List[trace.Op],
               text: Callable[[str], Optional[str]]) -> set:
    """The ``%name``s of the ops in one program run that made its GR-MAC
    launches' weights (``ops._cim_matmul_2d``): the op named as a launch's
    weight operand (the re-quantisation, which casts, scales and rounds),
    the ops it was made from back to the program's parameters (the tied
    head's copy of the embedding), and each op that reduces one of their
    array operands, a weight, to a scalar (its absmax scale). Loops and
    launches are never counted. ``text`` gives an op's text by name."""
    in_run = {op_head(o): o for o in ops}
    todo = [kernel_weight(o) for o in ops if trace.is_kernel(o)]
    made, weights = set(), set()
    while todo:
        h = todo.pop()
        o = in_run.get(h)
        if (o is None or h in made or trace.is_kernel(o)
                or trace.is_container(o)):
            continue
        made.add(h)
        for typ, name in operands(text(h)):
            if not _SCALAR.fullmatch(typ):
                weights.add(name)
            todo.append(name)
    for o in ops:
        if trace.is_kernel(o) or trace.is_container(o):
            continue
        t = text(op_head(o))
        if makes_scalar(t) and weights & {n for _, n in operands(t)}:
            made.add(op_head(o))
    return made


def prep_ns(ops: List[trace.Op], heads: set) -> int:
    """Device ns of the ops of one program run named in ``heads``, each
    counted once however many launches read it."""
    return sum(o.dur for o in ops
               if not trace.is_kernel(o) and op_head(o) in heads)


def self_ns(spans: List[tuple]) -> Dict[str, tuple]:
    """{span name: (count, total ns, self ns)}: a span's self time is its
    duration less the part of it that its child spans cover."""
    out: Dict[str, list] = {}
    stack: List[list] = []          # open spans: [end, name, children ns]

    def close(top):
        rec = out.setdefault(top[1], [0, 0, 0])
        rec[2] -= top[2]

    for name, start, dur, *_ in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][0] < start + dur:
            close(stack.pop())
        if stack:
            stack[-1][2] += dur
        rec = out.setdefault(name, [0, 0, 0])
        rec[0] += 1
        rec[1] += dur
        rec[2] += dur
        stack.append([start + dur, name, 0])
    while stack:
        close(stack.pop())
    return {k: tuple(v) for k, v in out.items()}


# --- the script ---------------------------------------------------------------
def report(devices, spans) -> List[str]:
    """Lines of kernel time by site, idle time by host span (chip 0), and
    the host spans' own time."""
    d = devices[0]
    sites = kernel_ns_by_site(d.ops)
    total = sum(sites.values())
    lines = [f"grmac_kernel {total / 1e9:.6f} s by site: " + ", ".join(
        f"{s or 'unsited'} {ns / 1e9:.6f}" for s, ns in
        sorted(sites.items(), key=lambda kv: -kv[1]))]
    idle = idle_by_span(d.ops, spans)
    all_idle = sum(idle.values())
    lines.append(f"device idle {all_idle / 1e9:.6f} s by innermost host "
                 "span: " + ", ".join(
                     f"{k or 'none'} {ns / 1e9:.6f}" for k, ns in
                     sorted(idle.items(), key=lambda kv: -kv[1])))
    idle_steps, n = idle_in_steps(d.ops, spans)
    if n:
        lines.append(f"{n} scheduler steps; device idle inside them "
                     f"{idle_steps / 1e9:.6f} s, {idle_steps / n / 1e6:.4f} "
                     "ms a step")
    lines.append("host spans (count, total s, self s): " + ", ".join(
        f"{k} {c} {t / 1e9:.6f} {own / 1e9:.6f}" for k, (c, t, own) in
        sorted(self_ns(spans).items(), key=lambda kv: -kv[1][2])))
    return lines


def with_operands(d: trace.Device, spans: List[tuple],
                  texts: Dict[str, Dict[str, str]]) -> trace.Device:
    """``d`` with the given host spans, and each op inside a program run
    named by its text with operands where ``texts`` has it."""
    starts = [m[1] for m in d.modules]
    ops = []
    for o in d.ops:
        i = bisect.bisect_right(starts, o.start) - 1
        m = d.modules[i] if i >= 0 else None
        t = (texts.get(m[0], {}).get(op_head(o))
             if m and o.start <= m[1] + m[2] else None)
        ops.append(trace.Op(t, o.start, o.dur) if t else o)
    return trace.Device(d.modules, ops, spans)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--trace-dir", required=True)
    ap.add_argument("--excerpt", help="write an excerpt of chip 0 here")
    ap.add_argument("--modules", type=int, default=12,
                    help="program runs the excerpt keeps")
    args = ap.parse_args(argv)
    devices = trace.load_xplane(args.trace_dir, 1)
    if not devices:
        print(f"no TPU device in the trace under {args.trace_dir}",
              file=sys.stderr)
        return 1
    path = newest_xplane(args.trace_dir)
    spans, _, texts = load_trace(path)
    for line in report(devices, spans):
        print(line)
    if args.excerpt:
        trace.save_excerpt([with_operands(devices[0], spans, texts)],
                           Path(args.excerpt), args.modules)
    return 0


if __name__ == "__main__":
    sys.exit(main())
