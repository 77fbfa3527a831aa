"""Run one benchmark cell once, on the chip this process finds.

    python3 -m benchmarks.chip.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration,
traffic mix and metrics are files under ``benchmarks/chip`` found by name
(``configs/``, ``traffic/``, ``metrics/``). The traffic file names the
kind (``kinds/<kind>.py``) that runs it; the configuration names its plain
reference (``reference/<name>.py``), which maps the file onto the
program's configuration and counts a step's operations and bytes. A
reference that lacks what the run calls is refused before set-up. Set-up (backend, weights, every shape the traffic uses)
counts as ``setup_s``; then the window runs for ``--seconds``; after it,
with the program's state freed, the plain reference decides ``correct``.

With ``--trace 0`` the result line carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer ones read from the profiler's trace of the
window. The last line of standard output is the result; the numbers that
decided ``correct`` are the last lines of standard error. Off the chip, or
with fewer chips than the cell needs, it exits non-zero and prints no
result. Stores and the trace live in a temporary directory (``$TMPDIR``)
removed at exit; the compile cache stays at ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

from benchmarks.chip import harness  # noqa: E402
from benchmarks.chip import trace as tracing  # noqa: E402


class Context:
    """What a kind's ``run`` is handed: the cell's files, the run's arguments, a
    work directory, host spans, and the window's trace switch."""

    def __init__(self, cell, seed, seconds, trace, work: Path):
        self.cell = cell
        self.config = harness.config(cell["config"])
        self.traffic = harness.traffic(cell["traffic"])
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.work = work
        self.trace_dir = work / "trace"
        self.spans = harness.Spans()

    def start_window(self) -> None:
        if self.trace:
            import jax

            jax.profiler.start_trace(str(self.trace_dir))
            self.spans.tracing = True

    def on_window_closed(self) -> None:
        if self.spans.tracing:
            import jax

            self.spans.tracing = False
            jax.profiler.stop_trace()


def read_metrics(h, out, device, red) -> dict:
    t0, t1 = out["window"]
    run = SimpleNamespace(
        out=out, config=h.config, traffic=h.traffic, cell=h.cell["name"],
        t0=t0, t1=t1, window_s=t1 - t0, setup_s=out["setup_end"] - T_START,
        trace=red, peaks=harness.peaks(device["kind"]), flops=harness.reference(h.config),
        spans=h.spans.records,
    )
    metrics = {}
    for m in harness.metrics_for(h.cell["name"], bool(h.trace)):
        value = harness.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def judge(numbers: dict, limits: dict) -> tuple[dict, bool]:
    """Each number compared beside its limit, and whether all are within."""
    compared = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    correct = all(harness.finite(v["value"]) and v["value"] <= v["limit"]
                  for v in compared.values())
    return compared, correct


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.workload(args.workload)
    kind = harness.kind_for(cell)
    harness.add_program_to_path()
    device = harness.require_chips(cell["chips"])
    from repro.utils import enable_compile_cache

    enable_compile_cache()
    work = Path(tempfile.mkdtemp(prefix="chipbench-"))
    try:
        h = Context(cell, args.seed, args.seconds, args.trace, work)
        out = kind.run(h)
        h.on_window_closed()
        red = tracing.reduce(tracing.load(h.trace_dir)) if args.trace else None
        metrics = read_metrics(h, out, device, red)
        written = harness.bytes_written()
        numbers = kind.check(h, out)
    finally:
        shutil.rmtree(work, ignore_errors=True)

    compared, correct = judge(numbers, h.config["check"])
    dev = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": correct, "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": dev}
    if red is not None:
        dev.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
    result["check"] = compared
    print(f"bytes written to storage by the run: {written}", file=sys.stderr)
    for k, v in compared.items():
        print(f"check {k} = {v['value']!r} (limit {v['limit']!r})", file=sys.stderr)
    print(f"correct = {correct}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
