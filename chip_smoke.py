"""Chip smoke: the training and serving paths, once, on a TPU at published widths.

    python chip_smoke.py             # one chip: kernels, serve, serve routed, train
    python chip_smoke.py --chips 4   # four chips: sharded train + restart on 1x2

Each phase runs in its own process, and this parent never imports JAX: a chip
belongs to one process at a time, and the routed serve phase needs its worker
process to hold it. Phases, in order:

* kernels — each Pallas kernel compiled for the chip (``tpu_custom_call`` in
  the program, never interpreted) and equal to its ``ref.py`` at real
  widths: ``changed_blocks`` over a (151936, 2048) bf16 leaf at 16 MiB
  chunks, ``flash_attention`` at H=16, Hkv=8, D=128, S=4096, and
  ``colocate_match`` at the size of ``examples/navp_colocation.py``.
* serve — ``qwen3-1.7b``, all 28 layers, through ``repro.launch.serve.main``
  in-process: 4 requests, prompt 128, gen 32. Then one request is published
  mid-generation (CAS v4), resumed in a fresh host and finished; its
  transcript must equal the uninterrupted one token for token.
* serve_routed — the same engine behind ``--workers 1``: the worker process
  holds the chip (its status must say ``tpu``), this phase's process never
  touches the device, and the transcripts equal the in-process ones.
* train — the Fig.-7 loop (``launch/train.py`` ``build_worker`` /
  ``run_preemptible``) on ``qwen3-1.7b`` widths with the depth cut to 4
  layers, twice: straight through, and reclaimed mid-run then resumed from
  the published CMI. Losses and the final state must be bit-identical.

``--chips 4`` runs only train_sharded: the depth-cut config on a 2x2
data×model mesh (a model-sharded leaf must span all 4 chips), reclaimed and
resumed on a 1x2 mesh (the restored state, gathered to the host, must equal
the published CMI bit for bit), finished, with its losses up to the reclaim
equal to an unpreempted 2x2 run.

The last line of standard output is ``{"ok": true, "device": {...}}``; any
failure exits non-zero without it. Where no TPU is found the first phase
fails. JAX's compilation cache is kept at ``$JAX_COMPILATION_CACHE_DIR`` when
set, else at ``<checkout>/.jax_cache``, so a second run compiles less.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import statistics
import subprocess
import sys
import threading
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
WORK = REPO / ".chip_smoke"  # stores and hand-over files; removed at the end
DEADLINE_S = 1150.0
PHASES = {1: ("kernels", "serve", "serve_routed", "train"), 4: ("train_sharded",)}

ARCH = "qwen3-1.7b"
SERVE_ARGV = ["--arch", ARCH, "--prompt-len", "128", "--gen", "32", "--batch", "4", "--seed", "0"]
TRAIN_LAYERS = 4  # depth cut; every width as published
TRAIN_ARGV = ["--arch", ARCH, "--seq-len", "1024", "--batch", "4", "--steps", "8",
              "--log-every", "1"]
TRAIN_RECLAIM = 4
SHARDED_ARGV = ["--arch", ARCH, "--seq-len", "1024", "--batch", "4", "--steps", "6",
                "--publish-every", "3", "--log-every", "1"]
SHARDED_RECLAIM = 3
DELTA_LEAF = (151936, 2048)  # qwen3-1.7b embedding, bf16
FLASH_QKV = (16, 8, 4096, 128)  # heads, kv heads, context, head dim
GRANULES = {"n_scans": 6, "viirs_pixels_per_scan": 1600, "viirs_lines_per_scan": 8}
RESULT_KEY = "chip_smoke_phase"


# ---------------------------------------------------------------------------
# phases (each in its own process)
# ---------------------------------------------------------------------------


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def require_tpu(phase: str) -> dict:
    import jax

    t0 = time.perf_counter()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU found: JAX sees {devs[0].platform} devices "
                         f"({len(devs)}); this smoke runs only on the chip")
    say(phase, f"backend up in {time.perf_counter() - t0:.1f}s")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def peak_gb() -> float:
    import jax

    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices()) / 1e9


def train_config():
    from repro.configs import get_config

    return get_config(ARCH).with_(n_layers=TRAIN_LAYERS)


def phase_kernels(work: Path) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint.serializer import _chunk_rows
    from repro.core import colocation as co
    from repro.kernels.colocate.ops import colocate_match
    from repro.kernels.colocate.ref import colocate_match_ref
    from repro.kernels.delta_encode.ops import changed_blocks
    from repro.kernels.delta_encode.ref import changed_blocks_ref
    from repro.kernels.flash_attention import attention_ref, flash_attention

    device = require_tpu("kernels")

    def compiled(name, fn, *args):
        exe = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in exe.as_text(), name
        return exe

    # delta_encode: qwen3-1.7b's embedding leaf at the publish chunk size
    t0 = time.perf_counter()
    shape = DELTA_LEAF
    rows = _chunk_rows(shape, 2, 16 << 20)
    old = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.bfloat16)
    touched = [3 * rows + 11, shape[0] - 1]
    new = old.at[touched[0], 7].add(1).at[touched[1], -1].set(-0.0)
    fn = compiled("delta_encode", lambda a, b: changed_blocks(a, b, rows, interpret=False), old, new)
    got = np.asarray(fn(old, new))
    # jitted: run op by op, the reference's chunk reshape is a relayout that
    # takes the TPU compiler over a minute on its own
    want = np.asarray(jax.jit(lambda a, b: changed_blocks_ref(a, b, rows))(old, new))
    np.testing.assert_array_equal(got, want)
    assert sorted(np.flatnonzero(got)) == sorted({r // rows for r in touched}), np.flatnonzero(got)
    assert not np.asarray(fn(old, old)).any()
    say("kernels", f"delta_encode {shape} bf16, {got.size} chunks of {rows} rows: "
                   f"changed {np.flatnonzero(got).tolist()} == ref "
                   f"({time.perf_counter() - t0:.1f}s with compiles)")

    # flash attention: qwen3-1.7b heads at a 4k context
    t0 = time.perf_counter()
    h, hkv, s, d = FLASH_QKV
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(kq, (1, h, s, d), jnp.bfloat16)
    k = jax.random.normal(kk, (1, hkv, s, d), jnp.bfloat16)
    v = jax.random.normal(kv, (1, hkv, s, d), jnp.bfloat16)
    fn = compiled("flash_attention", lambda q, k, v: flash_attention(q, k, v, interpret=False), q, k, v)
    got = np.asarray(fn(q, k, v), np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(attention_ref(q, k, v), np.float32)
    err = float(np.max(np.abs(got - want)))
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)  # bf16, as tests/test_kernels.py
    say("kernels", f"flash_attention H={h} Hkv={hkv} S={s} D={d} causal bf16: "
                   f"max |kernel-ref| {err} ({time.perf_counter() - t0:.1f}s with compiles)")

    # colocate: the example's granules
    t0 = time.perf_counter()
    g = {k: jnp.asarray(x) for k, x in co.make_synthetic_granules(0, **GRANULES).items()}
    los = co.cris_los_ecef(g["cris_lat"], g["cris_lon"], g["sat_pos"]).astype(jnp.float32)
    view = co.viirs_pos_ecef(g["viirs_lat"], g["viirs_lon"]) - g["sat_pos"][None, :]
    u = (view / jnp.linalg.norm(view, axis=1, keepdims=True)).astype(jnp.float32)
    fn = compiled("colocate", lambda u, l: colocate_match(u, l, interpret=False), u, los)
    gi, gc = (np.asarray(x) for x in fn(u, los))
    with jax.default_matmul_precision("highest"):
        ri, rc = (np.asarray(x) for x in colocate_match_ref(u, los))
    np.testing.assert_allclose(gc, rc, rtol=1e-5, atol=1e-6)
    # a differing index must be a tie at f32 resolution: same cosine either way
    un, ln = np.asarray(u, np.float64), np.asarray(los, np.float64)
    diff = np.flatnonzero(gi != ri)
    gap = np.abs(np.sum(un[diff] * ln[gi[diff]], 1) - np.sum(un[diff] * ln[ri[diff]], 1))
    assert np.all(gap <= 1e-6), (diff[:8], gap[:8])
    say("kernels", f"colocate N={u.shape[0]} M={los.shape[0]}: {u.shape[0] - diff.size} "
                   f"exact argmax matches, {diff.size} f32 ties "
                   f"({time.perf_counter() - t0:.1f}s with compiles)")
    say("kernels", f"peak_bytes_in_use {peak_gb():.3f} GB")
    return {"device": device}


def phase_serve(work: Path) -> dict:
    import numpy as np

    from repro.checkpoint import load_manifest
    from repro.configs import get_config
    from repro.core import DHP, NBS, JobStore
    from repro.launch import serve
    from repro.serve.engine import make_engine
    from repro.serve.worker import ServeHost

    device = require_tpu("serve")
    t0 = time.perf_counter()
    m = serve.main(SERVE_ARGV)
    wall = time.perf_counter() - t0
    trans = m["transcripts"]
    assert len(trans) == 4 and all(len(t) == 32 for t in trans.values()), trans
    say("serve", f"{ARCH} full ({get_config(ARCH).n_layers} layers): 4 requests prompt 128 "
                 f"gen 32 in {wall:.1f}s "
                 f"(engine build and compiles included); prefill {m['prefill_tok_s']:.1f} tok/s, "
                 f"decode {m['decode_tok_s']:.1f} tok/s after the first token")
    (work / "serve_transcripts.json").write_text(json.dumps(trans))

    # CMI round trip: publish mid-generation, resume in a fresh host, finish
    req = serve.build_requests(get_config(ARCH).vocab, batch=4, prompt_len=128, gen=32, seed=0)[0]
    engine = make_engine(f"model:{ARCH}:full:seed=0")  # what SERVE_ARGV builds
    jobs = JobStore(work / "serve" / "jobs")
    nbs = NBS(work / "serve" / "s3")
    for node in ("s0", "s1"):
        nbs.add_node(node, mesh=None)
    job_id = jobs.create_job({"req": req["id"]}).job_id
    host = ServeHost(engine, node_name="s0", dhp=DHP(nbs, "s0", jobs))
    host.admit(req["id"], req["prompt"], req["max_new"], job_id=job_id)
    for _ in range(15):
        host.step()
    t0 = time.perf_counter()
    pub = host.publish(req["id"])
    publish_s = time.perf_counter() - t0
    manifest = load_manifest(jobs.cmi_root(job_id), pub["cmi"])
    assert manifest.version == 4, manifest.version
    fresh = ServeHost(engine, node_name="s1", dhp=DHP(nbs, "s1", jobs))
    t0 = time.perf_counter()
    res = fresh.resume(req["id"], job_id)
    resume_s = time.perf_counter() - t0
    tokens = [t for _, t in res["tokens"]]
    while fresh.active:
        for toks in fresh.step()["tokens"].values():
            tokens.extend(t for _, t in toks)
    assert tokens == trans[req["id"]], (tokens, trans[req["id"]])
    state_mb = sum(np.asarray(a).nbytes for a in _leaves(host.active[req["id"]]["caches"])) / 1e6
    say("serve", f"CMI round trip of {req['id']}: published at done={pub['step']} "
                 f"(CAS v4, {state_mb:.1f} MB caches) in {publish_s:.3f}s, resumed in a fresh "
                 f"host in {resume_s:.3f}s, finished: 32 tokens identical to the uninterrupted run")
    say("serve", f"peak_bytes_in_use {peak_gb():.3f} GB")
    return {"device": device}


def _leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(tree)


def phase_serve_routed(work: Path) -> dict:
    # This process must never initialize a JAX backend: the worker it spawns
    # is the one process on the chip, and would fail if this one held it.
    from repro.launch import serve

    t0 = time.perf_counter()
    m = serve.main(SERVE_ARGV + ["--workers", "1"])
    wall = time.perf_counter() - t0
    status = m["workers"]["s0"]
    assert status["platform"] == "tpu", status
    want = json.loads((work / "serve_transcripts.json").read_text())
    assert m["transcripts"] == want, "routed transcripts differ from in-process"
    say("serve_routed", f"worker s0 ran {status['engine']} on {status['platform']}; "
                        f"4 requests in {wall:.1f}s (worker start included); TTFT p50 "
                        f"{m['ttft_p50_s'] * 1e3:.1f} ms, decode {m['decode_tok_s']:.1f} tok/s; "
                        "transcripts identical to the in-process run")
    return {"device": None}


def _digests(root: Path, name: str) -> dict:
    """Per-array chunk digests of a v4 CMI: equal digests, equal bytes."""
    from repro.checkpoint import load_manifest

    man = load_manifest(root, name)
    assert man.version == 4, man.version
    return {path: [(c.slice, c.file) for c in entry.chunks] for path, entry in man.arrays.items()}


def _train(store: Path, argv: list[str], cfg) -> dict:
    import repro.launch.train as T
    from repro.core import JobStore

    args = T.build_parser().parse_args(argv + ["--store", str(store)])
    history: list[dict] = []
    t0 = time.perf_counter()
    loss, job_id = T.run_job(args, cfg, history=history)
    wall = time.perf_counter() - t0
    jobs = JobStore(store)
    job = jobs.read_job(job_id)
    assert job.status == "finished", job.status
    return {"loss": loss, "job": job, "jobs": jobs, "history": history, "wall": wall}


def _param_count(cfg) -> int:
    import numpy as np

    from repro.distributed.steps import model_axes_for

    return sum(int(np.prod(s.shape)) for s in _leaves(model_axes_for(cfg)[1]))


def phase_train(work: Path) -> dict:
    device = require_tpu("train")
    cfg = train_config()
    say("train", f"{shutil.disk_usage(work).free / 1e9:.1f} GB free for the stores")
    a = _train(work / "train" / "straight", TRAIN_ARGV + ["--publish-every", "8"], cfg)
    da = _digests(a["jobs"].cmi_root(a["job"].job_id), a["job"].cmi)
    shutil.rmtree(work / "train" / "straight")  # a 7 GB state per CMI: keep one store
    b = _train(work / "train" / "reclaimed",
               TRAIN_ARGV + ["--publish-every", "8", "--preempt-at", str(TRAIN_RECLAIM)], cfg)
    db = _digests(b["jobs"].cmi_root(b["job"].job_id), b["job"].cmi)
    la = [h["loss"] for h in a["history"]]
    lb = [h["loss"] for h in b["history"]]
    assert [h["step"] for h in b["history"]] == list(range(1, 9)), b["history"]
    assert la == lb and a["loss"] == b["loss"], (la, lb)
    assert da == db, "final train state differs between the straight and reclaimed runs"
    # warm steps: neither an incarnation's first (compile) nor a publish step
    warm = [h["step_s"] for h in b["history"] + a["history"]
            if h["step"] not in (1, TRAIN_RECLAIM + 1)]
    pubs = [h["publish_s"] for h in a["history"] + b["history"] if h["publish_s"]]
    say("train", f"{ARCH} widths, n_layers 28 -> {TRAIN_LAYERS} (depth cut), "
                 f"{_param_count(cfg) / 1e9:.3f} B params + AdamW fp32 master/moments; "
                 "seq 1024 x batch 4, 8 steps")
    say("train", f"losses {la}")
    say("train", f"reclaimed at step {TRAIN_RECLAIM}, resumed from its CMI: 8 losses and the final "
                 f"state ({len(db)} arrays, {sum(map(len, db.values()))} chunk digests) "
                 "bit-identical to the straight run")
    say("train", f"step time after warm-up: median {statistics.median(warm):.4f}s over "
                 f"{len(warm)} steps (min {min(warm):.4f}s); first step (compile) "
                 f"{a['history'][0]['step_s']:.1f}s")
    say("train", f"publish times {[round(p, 3) for p in pubs]} s; run walls "
                 f"{a['wall']:.1f}s straight, {b['wall']:.1f}s reclaimed")
    say("train", f"peak_bytes_in_use {peak_gb():.3f} GB")
    return {"device": device}


def phase_train_sharded(work: Path) -> dict:
    import jax
    import numpy as np

    from repro.core.cmi import restore_cmi
    from repro.distributed.steps import make_train_step
    from repro.launch.train import parse_mesh
    from repro.optim import AdamWConfig

    device = require_tpu("train_sharded")
    if device["count"] < 4:
        raise SystemExit(f"--chips 4 needs 4 chips, JAX sees {device['count']}")
    cfg = train_config()

    def at_reclaim(run):
        jid = run["job"].job_id
        name = next(n for n in run["jobs"].list_cmis(jid) if int(n.split("-")[1]) == SHARDED_RECLAIM)
        return run["jobs"].cmi_root(jid), name

    a = _train(work / "sharded" / "straight", SHARDED_ARGV + ["--mesh", "2x2"], cfg)
    da = _digests(*at_reclaim(a))
    shutil.rmtree(work / "sharded" / "straight")
    b = _train(work / "sharded" / "reclaimed",
               SHARDED_ARGV + ["--remesh", "2x2,1x2", "--preempt-at", str(SHARDED_RECLAIM)], cfg)
    la = [h["loss"] for h in a["history"]]
    lb = [h["loss"] for h in b["history"]]
    assert [h["step"] for h in b["history"]] == list(range(1, 7)), b["history"]
    assert la[:SHARDED_RECLAIM] == lb[:SHARDED_RECLAIM], (la, lb)
    assert np.all(np.isfinite(lb)), lb
    say("train_sharded", f"losses 2x2 straight {la}")
    say("train_sharded", f"losses 2x2 -> 1x2    {lb}; equal up to the reclaim at step "
                         f"{SHARDED_RECLAIM}, max |diff| after {max(abs(x - y) for x, y in zip(la, lb))}")

    # the CMI published at the reclaim, restored as the worker restores it
    root, name = at_reclaim(b)
    assert _digests(root, name) == da, "states at the reclaim differ between the runs"
    published, _ = restore_cmi(root, name)  # host numpy, CRC-checked
    opt_cfg = AdamWConfig(moment_dtype=cfg.opt_moment_dtype)
    for spec in ("2x2", "1x2"):
        mesh = parse_mesh(spec)
        _, st_sh, _ = make_train_step(cfg, mesh, opt_cfg)
        state, _ = restore_cmi(root, name, mesh=mesh)
        state = jax.tree_util.tree_map(jax.device_put, state, st_sh)
        if spec == "2x2":
            leaf = state["params"]["blocks"]["g0"]["ffn"]["wg"]
            assert "model" in str(leaf.sharding.spec), leaf.sharding
            assert len(leaf.sharding.device_set) == 4, leaf.sharding
            say("train_sharded", f"on 2x2 ffn.wg {leaf.shape} is {leaf.sharding.spec} over "
                                 f"{len(leaf.sharding.device_set)} devices")
        else:
            flat_p, flat_s = _leaves(published), _leaves(jax.device_get(state))
            assert len(flat_p) == len(flat_s)
            for x, y in zip(flat_p, flat_s):
                assert np.asarray(x).tobytes() == np.asarray(y).tobytes()
            say("train_sharded", f"restored onto 1x2 and gathered: {len(flat_s)} arrays bitwise "
                                 f"equal to the CMI published at step {SHARDED_RECLAIM}")
        del state
    say("train_sharded", f"reclaimed run finished: loss {b['loss']}, job {b['job'].status}")
    say("train_sharded", f"peak_bytes_in_use (max over chips) {peak_gb():.3f} GB")
    return {"device": device}


def run_phase(phase: str, work: Path) -> int:
    sys.path.insert(0, str(REPO / "src"))
    from repro.utils import enable_compile_cache

    cache = Path(enable_compile_cache())

    def entries() -> int:
        return sum(1 for _ in cache.iterdir()) if cache.is_dir() else 0

    before = entries()
    t0 = time.perf_counter()
    out = globals()[f"phase_{phase}"](work)
    say(phase, f"{time.perf_counter() - t0:.1f}s; compile cache {cache}: "
               f"{before} -> {entries()} entries")
    print(json.dumps({RESULT_KEY: phase, "ok": True, **out}), flush=True)
    return 0


# ---------------------------------------------------------------------------
# parent: one process per phase, never touching JAX itself
# ---------------------------------------------------------------------------


def _run_child(phase: str, timeout_s: float) -> dict | None:
    cmd = [sys.executable, str(Path(__file__).resolve()), "--phase", phase, "--work", str(WORK)]
    # own session: a timeout takes down the phase and every process it started
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True, start_new_session=True)
    timer = threading.Timer(timeout_s, lambda: os.killpg(proc.pid, signal.SIGKILL))
    timer.start()
    result = None
    try:
        for line in proc.stdout:
            try:
                msg = json.loads(line)
            except ValueError:
                msg = None
            if isinstance(msg, dict) and RESULT_KEY in msg:
                result = msg
            else:
                print(line, end="", flush=True)
        rc = proc.wait()
    finally:
        timer.cancel()
        try:
            os.killpg(proc.pid, signal.SIGKILL)  # stragglers of a failed phase
        except ProcessLookupError:
            pass
    if rc != 0 or result is None or not result.get("ok"):
        print(f"chip_smoke: phase {phase} failed (rc={rc})", file=sys.stderr, flush=True)
        return None
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=sorted(PHASES), default=1)
    ap.add_argument("--phase", help=argparse.SUPPRESS)
    ap.add_argument("--work", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase:
        return run_phase(args.phase, Path(args.work))

    shutil.rmtree(WORK, ignore_errors=True)
    WORK.mkdir(parents=True)
    start = time.monotonic()
    device = None
    try:
        for phase in PHASES[args.chips]:
            remaining = DEADLINE_S - (time.monotonic() - start)
            result = _run_child(phase, remaining)
            if result is None:
                return 1
            if result["device"] is not None:
                if device is not None and result["device"] != device:
                    print(f"chip_smoke: device changed {device} -> {result['device']}", file=sys.stderr)
                    return 1
                device = result["device"]
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    print(f"chip_smoke: all phases passed in {time.monotonic() - start:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
