# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness entry point.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--lint]
                                            [--hop-out BENCH_hop.json]
                                            [--spot-out BENCH_spot.json]
                                            [--serve-out BENCH_serve.json]

Sections map to the paper's experiments (DESIGN.md §7):
    bench_ckpt     — Exp 2: C/R overhead + CMI size (full/delta/device-hint/async)
    bench_hop      — Exp 2: hop latency, live/store/xproc/stream/stream-delta
    bench_spot     — §2.2/Q1/Q2: spot-market cost model
    bench_serve    — elastic serving: tokens/s + TTFT under migration/resume churn
    bench_colocate — Exp 1: VIIRS→CrIS co-location stages + match kernel
    bench_train    — end-to-end smoke train step + publish cadence overhead

``--lint`` gates the run on navlint (``python -m repro.analysis``): the
migration-safety lint over src/ + examples/ plus the fault-coverage
checker. A tour that hops with an open file or publishes nondeterministic
state produces benchmark numbers that no resumed run can reproduce, so the
harness refuses to measure it.

``--hop-out`` also records the hop section as machine-readable JSON (schema
mirrors ``BENCH_ckpt.json``, with ``env.notes``) so the transport's perf
trajectory is comparable across PRs; ``--spot-out`` does the same for the
spot cadence-policy sweep (goodput per policy per hazard trace), and
``--serve-out`` for the serving-fleet churn legs (single vs migrate vs
resume, transcripts asserted bit-identical first).
"""

from __future__ import annotations

import json
import sys


def _section(name: str, rows) -> None:
    for n, us, derived in rows:
        print(f"{name}.{n},{us:.1f},{derived}")


def bench_train_rows(fast: bool) -> list[tuple[str, float, str]]:
    """Train-step wall time + publish overhead on a smoke config (CPU)."""
    import time

    import jax

    from repro.configs import get_smoke_config
    from repro.core import DHP, NBS, JobStore
    from repro.data import TokenPipeline
    from repro.distributed.steps import batch_shardings, make_init_fn, make_train_step
    from repro.launch.mesh import auto_mesh
    from repro.optim import AdamWConfig
    import tempfile

    cfg = get_smoke_config("qwen3-1.7b")
    mesh = auto_mesh((1, 1), ("data", "model"))
    oc = AdamWConfig()
    init_fn, _ = make_init_fn(cfg, mesh, oc)
    step_fn, st_sh, m_sh = make_train_step(cfg, mesh, oc, peak_lr=1e-3, warmup=1)
    state = init_fn()
    pipe = TokenPipeline(cfg, 64, 4)
    batch, _ = pipe.batch_at(pipe.init_state())
    bsh = batch_shardings(jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch), mesh)
    batch = jax.tree_util.tree_map(jax.device_put, batch, bsh)
    jstep = jax.jit(step_fn, in_shardings=(st_sh, bsh), out_shardings=(st_sh, m_sh), donate_argnums=0)
    state, m = jstep(state, batch)  # compile
    jax.block_until_ready(m["loss"])
    n = 3 if fast else 10
    t0 = time.perf_counter()
    for _ in range(n):
        state, m = jstep(state, batch)
    jax.block_until_ready(m["loss"])
    dt = (time.perf_counter() - t0) / n
    rows = [("train_step", dt * 1e6, f"smoke qwen3 seq64 b4 loss={float(m['loss']):.3f}")]
    root = tempfile.mkdtemp(prefix="bench-train-")
    store = JobStore(root)
    nbs = NBS(root + "/nbs")
    nbs.add_node("n0", mesh=mesh)
    dhp = DHP(nbs, "n0", store)
    job = store.create_job({})
    t0 = time.perf_counter()
    dhp.publish(job.job_id, "ckpt", state, step=1)
    t_pub = time.perf_counter() - t0
    rows.append(("publish_ckpt", t_pub * 1e6, f"{t_pub/dt:.1f} steps of overhead per publish"))
    return rows


def main() -> None:
    if "--lint" in sys.argv:
        from pathlib import Path

        from repro.analysis import main as navlint

        repo = Path(__file__).resolve().parent.parent
        rc = navlint(["--check", str(repo / "src"), str(repo / "examples"),
                      "--coverage", "--docs", str(repo / "docs" / "fabric.md")])
        if rc:
            raise SystemExit(rc)
    fast = "--fast" in sys.argv
    hop_out = spot_out = None
    if "--hop-out" in sys.argv:
        i = sys.argv.index("--hop-out") + 1
        if i >= len(sys.argv) or sys.argv[i].startswith("--"):
            raise SystemExit("--hop-out needs a file path argument")
        hop_out = sys.argv[i]
    if "--spot-out" in sys.argv:
        i = sys.argv.index("--spot-out") + 1
        if i >= len(sys.argv) or sys.argv[i].startswith("--"):
            raise SystemExit("--spot-out needs a file path argument")
        spot_out = sys.argv[i]
    serve_out = None
    if "--serve-out" in sys.argv:
        i = sys.argv.index("--serve-out") + 1
        if i >= len(sys.argv) or sys.argv[i].startswith("--"):
            raise SystemExit("--serve-out needs a file path argument")
        serve_out = sys.argv[i]
    print("name,us_per_call,derived")
    from benchmarks import bench_ckpt, bench_colocate, bench_hop, bench_spot

    _section("ckpt", bench_ckpt.run(16 if fast else 64))
    hop_rows, hop_results = bench_hop.bench(16 if fast else 64)
    _section("hop", hop_rows)
    if hop_out:
        with open(hop_out, "w") as f:
            json.dump(hop_results, f, indent=1, sort_keys=True)
    spot_rows, spot_results = bench_spot.bench(
        work_steps=1200 if fast else 4000, trials=3 if fast else 5)
    _section("spot", spot_rows)
    if spot_out:
        with open(spot_out, "w") as f:
            json.dump(spot_results, f, indent=1, sort_keys=True)
    from benchmarks import bench_serve

    serve_rows, serve_results = bench_serve.bench(
        n_requests=6 if fast else 8, gen=16 if fast else 32)
    _section("serve", serve_rows)
    if serve_out:
        with open(serve_out, "w") as f:
            json.dump(serve_results, f, indent=1, sort_keys=True)
    _section("colocate", bench_colocate.run(2 if fast else 4))
    _section("train", bench_train_rows(fast))


if __name__ == "__main__":
    main()
