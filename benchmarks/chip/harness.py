"""What every cell shares: finding its files by name (the configuration's
reference among them), the chip check, seeds, host spans, the
metric readers and the result line.

Nothing here imports JAX at module level: the chip check must run before
any backend starts.
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from typing import Any

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]


class NoChip(SystemExit):
    """No TPU, or fewer chips than the cell asks for: exit non-zero, print no result."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(name: str) -> dict:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def peaks(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def derived_seed(seed: int, salt: str = "") -> int:
    """A 31-bit seed from any integer: JAX keys and int32 state fields take it."""
    digest = hashlib.blake2b(f"{int(seed)}:{salt}".encode(), digest_size=8).digest()
    return int.from_bytes(digest, "little") & 0x7FFFFFFF


def add_program_to_path() -> None:
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def require_chips(n: int) -> dict:
    """The device record of the result line; raises :class:`NoChip` off the chip."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU found: JAX sees {len(devs)} {devs[0].platform} device(s)")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": n}


def memory_peak_bytes() -> int:
    """Peak device memory on the fullest chip. On the TPU ``peak_bytes_in_use``
    counts the arrays alone and leaves out a program's temporaries, which the
    runtime holds in its reservation (``peak_bytes_reserved``): the larger of
    the two is what the chip had to hold."""
    import jax

    def peak(d) -> int:
        stats = d.memory_stats() or {}
        return max(int(stats.get("peak_bytes_in_use", 0)), int(stats.get("peak_bytes_reserved", 0)))

    return max(peak(d) for d in jax.local_devices())


def bytes_written() -> int | None:
    """Bytes this process has caused to be written to storage (Linux)."""
    try:
        with open("/proc/self/io") as f:
            return next(int(ln.split()[1]) for ln in f if ln.startswith("write_bytes"))
    except (OSError, StopIteration):
        return None


# Where ``reference(c)`` looks for ``<c["reference"]>.py`` (a test points it elsewhere).
REFERENCE_PACKAGE = "benchmarks.chip.reference"
# What every run calls of a reference module; each kind adds its own (``kinds/<kind>.REFERENCE``).
REFERENCE_NEEDS = ("make_params", "arch_fields")


def reference(c: dict):
    """The plain reference module the configuration names (``reference/__init__.py``
    states what it keeps). It also counts the operations and bytes of the
    architecture's steps: the metric readers get it as ``run.flops``."""
    return importlib.import_module(f"{REFERENCE_PACKAGE}.{c['reference']}")


def kind_for(cell: dict):
    """The kind module that runs the cell's traffic (``kinds/<kind>.py``).

    Before any set-up, it refuses in one line a configuration whose reference
    module is missing or lacks what the run will call of it.
    """
    c = config(cell["config"])
    kind = importlib.import_module(f"benchmarks.chip.kinds.{traffic(cell['traffic'])['kind']}")
    refuse = f"configuration {c['name']!r}:"
    name = f"{REFERENCE_PACKAGE}.{c.get('reference')}"
    if "reference" not in c or importlib.util.find_spec(name) is None:
        raise SystemExit(f"{refuse} no reference module {name}")
    missing = [f for f in REFERENCE_NEEDS + kind.REFERENCE if not hasattr(reference(c), f)]
    if missing:
        raise SystemExit(f"{refuse} reference {name} lacks {', '.join(missing)}")
    return kind


def program_config(c: dict):
    """The program's ``ArchConfig`` for a configuration file: the registry
    entry of ``program.arch`` with every size the file states, mapped by the
    configuration's reference (``arch_fields``)."""
    from repro.configs import get_config

    return get_config(c["program"]["arch"]).with_(**reference(c).arch_fields(c))


class Spans:
    """Host spans: kept in memory always, and written into the profiler's
    trace as ``TraceAnnotation`` while a trace is being taken."""

    def __init__(self) -> None:
        self.records: list[tuple[str, float, float]] = []
        self.tracing = False
        self._open: dict[str, tuple[float, Any]] = {}

    def begin(self, name: str) -> None:
        ann = None
        if self.tracing:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        self._open[name] = (time.perf_counter(), ann)

    def end(self, name: str) -> float | None:
        if name not in self._open:
            return None
        t0, ann = self._open.pop(name)
        if ann is not None:
            ann.__exit__(None, None, None)
        t1 = time.perf_counter()
        self.records.append((name, t0, t1))
        return t1

    def close_all(self) -> None:
        for name in list(self._open):
            self.end(name)


def reader(name: str):
    """The reader of metric ``name``: ``metrics/<name>.py``, its ``read(run)``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("chip_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(cell: str, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics (``trace`` false) or per-layer ones."""
    bench = benchmark()
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def finite(x: float | None) -> bool:
    return x is not None and math.isfinite(x)
