"""Training traffic: one job driven through the program's Fig.-7 loop.

The harness builds the job's first state from the seed (weights as served,
float32 master, zero moments) in one jitted call and publishes it through
the program's own ``DHP``; then ``launch/train.py``'s ``build_worker`` runs
under ``run_preemptible`` with the harness's ``history``, ``NBS``, plugin
subscriptions and reclaim schedule. Set-up reclaims the job at the steps
the traffic file lists (each publishes, and the next incarnation restores
and re-jits), so that the window's publishes have a delta parent and its
resumes compile nothing. The window opens at the first step boundary after
the last set-up reclaim and closes at the first one after ``--seconds``.

Traffic parameters (``traffic/<name>.json``): ``seq_len``, ``batch``,
``publish_every`` (0: none in the window), ``reclaim_every`` (0: none),
``setup_reclaims``.

``correct`` compares the job's first three steps with the plain reference:
the first clipped gradient per leaf (from the first-moment state the
program published after step 1) and the change of the float32 master
weights per leaf after step 3 (from the state it published then). Each
step's loss (from ``history``) is read beside them as ``loss_gap``; the
configuration's ``check`` names the numbers that are held to a limit.
The two published states are hard-linked aside when they commit, since
the job store's GC drops them later.
"""

from __future__ import annotations

import gc
import os
import time
from pathlib import Path

import numpy as np

from benchmarks.chip import harness

CAPTURE_STEPS = (1, 3)
# What this kind calls of the configuration's reference, beyond what every run does.
REFERENCE = ("flat", "train_steps", "train_step_flops")


class WindowClosed(Exception):
    """Raised from ``history.append`` at the first step boundary after the window."""


class History(list):
    """The ``history`` list handed to ``build_worker``: each entry the
    program appends is stamped and handed to ``on_step``."""

    def __init__(self, on_step):
        super().__init__()
        self._on_step = on_step

    def append(self, entry):
        entry = dict(entry, t=time.perf_counter())
        super().append(entry)
        self._on_step(entry)


def token_batch(seed: int, data_step: int, batch: int, seq_len: int, vocab: int):
    """The job's token stream at ``data_step``: counter-based Philox keyed by
    the seed, Zipf(1.3) ids modulo the vocabulary, labels shifted by one."""
    rng = np.random.Generator(np.random.Philox(key=int(seed), counter=int(data_step)))
    full = (rng.zipf(1.3, size=(batch, seq_len + 1)).astype(np.int64) % vocab).astype(np.int32)
    return full[:, :-1], full[:, 1:]


def initial_state(c: dict, seed: int):
    """Step-0 train state on the device from ``seed``.

    The weights come from the reference's own ``make_params``, the very
    program the check calls again: the same jitted function rounds alike,
    where the same code traced inside a larger program need not (on the
    TPU such a copy drew weights one bfloat16 step apart). The optimizer
    state around them is one more jitted call.
    """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def around(params, s):
        f32 = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params)
        return {
            "params": params,
            "opt": {"mu": jax.tree_util.tree_map(jnp.zeros_like, f32),
                    "nu": jax.tree_util.tree_map(jnp.zeros_like, f32),
                    "master": f32, "count": jnp.zeros((), jnp.int32)},
            "step": jnp.zeros((), jnp.int32),
            "rng": jnp.stack([jnp.uint32(0), s + jnp.uint32(1)]),
            "data": {"data_step": jnp.zeros((), jnp.int32), "seed": s.astype(jnp.int32)},
        }

    return around(harness.reference(c).make_params(c, seed, c["torch_dtype"]), jnp.uint32(seed))


def _check_layout(state, cfg) -> None:
    """The state must have exactly the program's train-state layout."""
    import jax

    from repro.distributed.steps import state_struct_for
    from repro.optim import AdamWConfig

    want = state_struct_for(cfg, AdamWConfig(moment_dtype=cfg.opt_moment_dtype))
    got_paths = jax.tree_util.tree_flatten_with_path(state)[0]
    want_paths = jax.tree_util.tree_flatten_with_path(want)[0]
    got = [(jax.tree_util.keystr(p), x.shape, str(x.dtype)) for p, x in got_paths]
    exp = [(jax.tree_util.keystr(p), x.shape, str(x.dtype)) for p, x in want_paths]
    if got != exp:
        diff = [(g, e) for g, e in zip(got, exp) if g != e][:4]
        raise RuntimeError(f"initial state does not match the program's layout: {diff}")


def _capture(src: Path, name: str, dest: Path) -> None:
    """Hard-link one committed CMI and the objects it may reference."""
    for top in (src / name, src / "objects"):
        for f in top.rglob("*") if top.is_dir() else ():
            target = dest / f.relative_to(src)
            if f.is_file() and not target.exists():
                target.parent.mkdir(parents=True, exist_ok=True)
                os.link(f, target)


def run(h) -> dict:
    from repro.core import DHP, NBS, JobStore
    from repro.core.preemption import PreemptionNotice, SpotSchedule, run_preemptible
    from repro.launch import train as T

    c, tr = h.config, h.traffic
    cfg = harness.program_config(c)
    seed = harness.derived_seed(h.seed, "train")
    opt = c["optimizer"]
    seq, batch = tr["seq_len"], tr["batch"]
    start_step = max(tr["setup_reclaims"]) + 1
    reclaims = set(tr["setup_reclaims"])
    if tr["reclaim_every"]:
        reclaims |= {k for k in range(start_step + 1, 100_000) if k % tr["reclaim_every"] == 0}
    args = T.build_parser().parse_args([
        "--arch", c["program"]["arch"], "--seq-len", str(seq), "--batch", str(batch),
        "--steps", str(10**9), "--publish-every", str(tr["publish_every"] or 10**9),
        "--peak-lr", str(opt["peak_lr"]), "--warmup", str(opt["warmup"]),
        "--seed", str(seed), "--store", str(h.work / "jobs"), "--log-every", "0",
    ])
    store = JobStore(args.store)
    nbs = NBS(str(h.work / "nbs"))
    capture = h.work / "captured"
    spans = h.spans

    state0 = initial_state(c, seed)
    _check_layout(state0, cfg)
    job_id = store.create_job({"cell": h.cell["name"]}).job_id
    nbs.add_node("bench-setup", mesh=None)
    DHP(nbs, "bench-setup", store).publish(job_id, "ckpt", state0, step=0)
    del state0

    rec = {"steps": [], "publishes": [], "captured": {}, "t0": None, "t1": None}

    def on_checkpoint(node, cmi, step):
        spans.begin("bench.publish")

    def on_publish(job_id, status, name):
        t = spans.end("bench.publish")
        step = int(name.split("-")[1]) if name else None
        rec["publishes"].append({"step": step, "t": t})
        if status == "ckpt" and step in CAPTURE_STEPS:
            _capture(store.cmi_root(job_id), name, capture)
            rec["captured"][step] = name

    def on_restart(node, cmi, step):
        if spans.end("bench.resume") is not None:
            spans.begin("bench.first_step")
        rec["restart_pending"] = True

    for event, fn in (("on_checkpoint", on_checkpoint), ("on_publish", on_publish),
                      ("on_restart", on_restart)):
        nbs.plugins.subscribe(event, fn)

    def on_step(entry):
        k = entry["step"]
        first = rec.pop("restart_pending", False)
        spans.end("bench.first_step") if first else spans.end("bench.step")
        in_window = rec["t0"] is not None
        rec["steps"].append(dict(entry, first=first, reclaim=k in reclaims, window=in_window))
        if k == start_step and not in_window:
            h.start_window()
            rec["t0"] = time.perf_counter()
            spans.begin("bench.window")
        elif in_window and entry["t"] - rec["t0"] >= h.seconds:
            rec["t1"] = entry["t"]
            spans.end("bench.window")
            raise WindowClosed
        spans.begin("bench.resume" if k in reclaims else "bench.step")

    schedule = SpotSchedule(preempt_steps=tuple(sorted(reclaims)))
    make_worker = T.build_worker(args, cfg, store, nbs, schedule, PreemptionNotice(), job_id,
                                 ["1x1"], History(on_step))
    try:
        run_preemptible(make_worker, max_restarts=10_000)
    except WindowClosed:
        pass
    spans.close_all()
    h.on_window_closed()
    memory = harness.memory_peak_bytes()
    del make_worker
    gc.collect()
    return {"kind": "train", "record": rec, "memory_peak_bytes": memory,
            "setup_end": rec["t0"], "window": (rec["t0"], rec["t1"]),
            "attempted": sum(1 for s in rec["steps"] if s["window"]), "failed": 0,
            "capture": capture, "seed31": seed, "cfg": cfg}


def check(h, out: dict, fmt=None) -> dict:
    """The three numbers that decide ``correct``, against the reference.

    With ``fmt`` the reference in that operand format stands in for the
    program (the control), and the program's own numbers are not read.
    """
    import jax

    from repro.core.cmi import restore_cmi

    c, tr, opt = h.config, h.traffic, h.config["optimizer"]
    ref = harness.reference(c)
    seed = out["seed31"]
    vocab, seq, batch = c["vocab_size"], tr["seq_len"], tr["batch"]
    batches = [token_batch(seed, k, batch, seq, vocab) for k in range(3)]
    params0 = ref.make_params(c, seed, c["torch_dtype"])
    master0 = {k: np.asarray(v, np.float32) for k, v in ref.flat(jax.device_get(params0)).items()}
    want = ref.train_steps(c, opt, params0, batches)
    if fmt is None:
        steps = {s["step"]: s for s in out["record"]["steps"]}
        losses = [steps[k]["loss"] for k in (1, 2, 3)]
        s1, _ = restore_cmi(out["capture"], out["record"]["captured"][1])
        s3, _ = restore_cmi(out["capture"], out["record"]["captured"][3])
        grad = {k: np.asarray(v, np.float64) / (1 - opt["b1"])
                for k, v in ref.flat(s1["opt"]["mu"]).items()}
        master = {k: np.asarray(v, np.float64) for k, v in ref.flat(s3["opt"]["master"]).items()}
    else:
        got = ref.train_steps(c, opt, params0, batches, fmt=fmt)
        losses, grad, master = got["losses"], got["first_grad"], got["master"]
    del params0
    return compare(losses, grad, master, want, master0)


def compare(losses, grad, master, want, master0) -> dict:
    """Loss gap, and the worst leaf's gap of gradient and update norms."""
    gr = {k: float(np.linalg.norm(np.asarray(v, np.float64))) for k, v in want["first_grad"].items()}
    med = float(np.median(list(gr.values())))
    moving = [k for k in gr if gr[k] >= 1e-3 * med]

    def norm(x):
        return float(np.linalg.norm(np.asarray(x, np.float64)))

    def worst(pairs):
        return max(abs(a - b) / max(b, m) for a, b, m in pairs)

    grad_gap = worst([(norm(grad[k]), gr[k], med) for k in gr])
    du_ref = {k: norm(np.asarray(want["master"][k], np.float64) - master0[k]) for k in moving}
    du_med = float(np.median(list(du_ref.values())))
    update_gap = worst([(norm(np.asarray(master[k], np.float64) - master0[k]), du_ref[k], du_med)
                        for k in moving])
    loss_gap = max(abs(a - b) for a, b in zip(losses, want["losses"]))
    if not all(np.isfinite(losses)):
        loss_gap = update_gap = float("inf")
    return {"loss_gap": loss_gap, "grad_gap": grad_gap, "update_gap": update_gap,
            "excluded_leaves": sorted(set(gr) - set(moving))}
