"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 -m benchmarks.chip.readings --workload train-ckpt --seeds 12 --control 3
    python3 -m benchmarks.chip.readings --workload serve-chat --seeds 12 --control 3 --seconds 25
    python3 -m benchmarks.chip.readings --workload train-ckpt --seeds 3 --control 0 --fault half_batch

In one process, for each seed: the cell's kind runs its set-up and a short
window through the program's own timed path, then the comparison with the
reference gives the numbers ``correct`` is decided by. For the first
``--control`` seeds the control is read as well: the reference in the
nearest precision below the configuration's (fp8 operands) in the program's
place. One JSON line per seed, then the largest sound reading and the
smallest control reading of each number. With ``--fault`` the program runs
with that fault planted (``faults.py``): its readings are upper ones.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

from benchmarks.chip import harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=0.5)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)

    from benchmarks.chip import faults
    from benchmarks.chip.run import Context, judge

    cell = harness.workload(args.workload)
    kind = harness.kind_for(cell)
    harness.add_program_to_path()
    harness.require_chips(cell["chips"])
    from repro.utils import enable_compile_cache

    enable_compile_cache()
    if args.fault:
        faults.plant(faults.Patcher(), args.fault)
    sound: dict[str, list] = {}
    control: dict[str, list] = {}
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        work = Path(tempfile.mkdtemp(prefix="chipbench-readings-"))
        try:
            h = Context(cell, seed, args.seconds, 0, work)
            out = kind.run(h)
            numbers = kind.check(h, out)
            row = {"seed": seed, "fault": args.fault, "program": numbers,
                   "correct": judge(numbers, h.config["check"])[1]}
            if i < args.control:
                numbers = kind.check(h, out, fmt="fp8")
                row["control"] = numbers
                row["control_correct"] = judge(numbers, h.config["check"])[1]
        finally:
            shutil.rmtree(work, ignore_errors=True)
        for k in h.config["check"]:
            sound.setdefault(k, []).append(row["program"][k])
            if "control" in row:
                control.setdefault(k, []).append(row["control"][k])
        print(json.dumps(row), flush=True)
    print(json.dumps({"lower": {k: max(v) for k, v in sound.items()},
                      "upper": {k: min(v) for k, v in control.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
