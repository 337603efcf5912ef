"""What every cell shares: finding a cell's files by name, the program's
configuration objects, the device check, the compile cache, per-layer
metric readers and the result line."""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from typing import Callable, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
SRC = ROOT / "src"


class NoDevice(RuntimeError):
    """The machine lacks the accelerator or the chips a cell needs."""


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"missing {path}")
    return json.loads(path.read_text())


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell_entry(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_cell(name: str, bench: Optional[dict] = None,
              base: Path = BENCH_DIR) -> dict:
    """The cell's BENCHMARK.json entry with its settings, configuration
    and traffic mix, each read from the file that bears its name."""
    bench = bench or benchmark()
    entry = cell_entry(bench, name)
    return {"entry": entry,
            "settings": load_json(base / "workloads" / f"{name}.json"),
            "spec": load_json(base / "configs" / f"{entry['config']}.json"),
            "mix": load_json(base / "traffic" / f"{entry['traffic']}.json")}


def import_program():
    """Put the program's sources on the path; fail where they are absent."""
    if not (SRC / "repro").is_dir():
        raise FileNotFoundError(
            f"the program under test is not in this checkout ({SRC})")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def strict_precision() -> None:
    """Have XLA round every value to the dtype the program gives it.

    By default XLA may keep a bfloat16 intermediate in float32 inside a
    fusion (``xla_allow_excess_precision``), so the compiled program does
    not compute in the precision the configuration states, and which
    roundings it skips depends on how the compiler fuses. Call before JAX
    starts its backend."""
    import os
    flag = "--xla_allow_excess_precision=false"
    flags = os.environ.get("XLA_FLAGS", "")
    if flag not in flags.split():
        os.environ["XLA_FLAGS"] = f"{flags} {flag}".strip()


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one; every program is kept."""
    import os

    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_tpu and (d.platform != "tpu" or len(devs) < chips):
        raise NoDevice(f"cell needs {chips} TPU chip(s); JAX found "
                       f"{len(devs)} {d.platform} device(s) ({d.device_kind})")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def peak_bytes(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def arch_from_spec(spec: dict):
    """The program's ArchConfig for a configuration file."""
    from repro.configs.base import ArchConfig
    from repro.core.cim_config import CIMConfig
    from repro.core.formats import parse_format
    c = spec["cim"]
    cim = CIMConfig(mode=c["mode"], granularity=c["granularity"],
                    fmt_x=parse_format(c["fmt_x"]),
                    fmt_w=parse_format(c["fmt_w"]), n_r=c["n_r"],
                    enob=float(c["enob"]), backend=c["backend"])
    a = dict(spec["arch"])
    a["block_pattern"] = tuple(a["block_pattern"])
    return ArchConfig(cim=cim, **a)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (the ceil(q * n)-th smallest value)."""
    xs = sorted(values)
    if not xs:
        return math.nan
    return xs[max(0, math.ceil(q * len(xs)) - 1)]


class CompileCounter:
    """Counts backend compilations (a program not found in any cache)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1


def metric_reader(name: str, base: Path = BENCH_DIR) -> Callable:
    """``metrics/<name>.py``'s ``read``."""
    path = base / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_layer_metrics(bench: dict, cell: str, run,
                       base: Path = BENCH_DIR) -> dict:
    """Every per-layer metric that BENCHMARK.json lists for ``cell``;
    a reader that finds nothing returns None and is left out."""
    e2e = {m["name"] for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])}
    out = {}
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if cells is None and m["moves"] not in e2e:
            continue
        if cells is not None and cell not in cells:
            continue
        value = metric_reader(m["name"], base)(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def e2e_metrics(bench: dict, cell: str, values: dict) -> dict:
    out = {}
    for m in bench["end_to_end"]:
        if cell in m.get("workloads", [cell]):
            out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out


@dataclasses.dataclass
class Check:
    """One number compared with its limit: correct while ``value <= limit``
    (or ``value >= limit`` for a floor)."""
    name: str
    value: float
    limit: float
    floor: bool = False

    @property
    def ok(self) -> bool:
        if self.floor:
            return self.value >= self.limit
        return self.value <= self.limit


def emit(result: dict, checks: list, notes: list) -> None:
    """Earlier lines, the checks on standard error, and the result line
    with the checks as its last key."""
    for line in notes:
        print(line, flush=True)
    for c in checks:
        bound = "at least" if c.floor else "at most"
        print(f"check {c.name} = {c.value!r} ({bound} {c.limit!r}): "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    print(json.dumps(result), flush=True)


def now() -> float:
    return time.perf_counter()
