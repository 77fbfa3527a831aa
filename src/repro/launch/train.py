"""Preemptible training driver — the paper's Figure 7 loop, end to end.

    (1) request svc/get_job to get job_id/status
    (2) if status == "new":   main(job_id)          # fresh start
    (4) elif status == "ckpt": DHP.restart(job_id)   # resume from CMI
    ...
    (9/12) DHP.publish(job_id, "ckpt")    at application-chosen boundaries
    (15)   DHP.publish(job_id, "finished")

plus the spot-market supervision loop: on a (simulated or SIGTERM) 2-minute
notice the worker finishes its step, publishes, and exits; the supervisor
provisions the next incarnation — possibly with a *different mesh shape*
(elastic restart; ``--remesh``), which exercises CMI mesh-remapping.

Example (laptop scale):

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \
        --steps 30 --publish-every 10 --preempt-at 17 --store /tmp/navp-jobs
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import SHAPES, get_config, get_smoke_config
from repro.core import DHP, NBS, JobStore
from repro.core.delta import DeltaPolicy
from repro.core.dhp import Preempted
from repro.core.preemption import PreemptionNotice, SpotSchedule, run_preemptible
from repro.data import TokenPipeline
from repro.distributed.steps import batch_shardings, make_init_fn, make_train_step
from repro.launch.mesh import auto_mesh
from repro.optim import AdamWConfig
from repro.utils import enable_compile_cache, logger


def parse_mesh(spec: str):
    dims = [int(x) for x in spec.split("x")]
    names = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
    return auto_mesh(dims, names[: len(dims)])


def build_worker(args, cfg, store, nbs, schedule, notice, job_id, mesh_specs,
                 history: list | None = None):
    """Worker factory for :func:`run_preemptible`. With ``history``, every
    step appends ``{step, loss, step_s, publish_s}``: the loss as a float,
    the step's wall time up to the loss reaching the host, and the time the
    loop stood still for a publish (0.0 on steps without one)."""

    def make_worker(incarnation: int):
        def worker():
            mesh = parse_mesh(mesh_specs[min(incarnation, len(mesh_specs) - 1)])
            node = f"instance-{incarnation}"
            if node not in nbs.nodes:
                nbs.add_node(node, mesh=mesh)
            dhp = DHP(
                nbs, node, store,
                delta=DeltaPolicy(enabled=not args.no_delta),
                async_publish=args.async_publish,
            )
            opt_cfg = AdamWConfig(moment_dtype=cfg.opt_moment_dtype)
            step_fn, st_sh, m_sh = make_train_step(
                cfg, mesh, opt_cfg, peak_lr=args.peak_lr, warmup=args.warmup,
                total_steps=args.steps,
            )
            pipe = TokenPipeline(cfg, args.seq_len, args.batch, seed=args.seed)
            job = store.svc_get_job(job_id, worker=node)
            if job.status == "ckpt":
                state, _ = dhp.restart(job_id, node=node)
                # re-pin to this incarnation's canonical shardings (no-op when
                # the mesh matches; a resharding copy when it doesn't)
                state = jax.tree_util.tree_map(jax.device_put, state, st_sh)
                logger.info("resumed job %s at step %d on %s", job_id, int(state["step"]), node)
            else:
                init_fn, _ = make_init_fn(cfg, mesh, opt_cfg, seed=args.seed)
                state = init_fn()
                logger.info("fresh start for job %s on %s", job_id, node)

            bstruct = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                pipe.batch_at(pipe.init_state())[0],
            )
            b_sh = batch_shardings(bstruct, mesh)
            jstep = jax.jit(
                step_fn, in_shardings=(st_sh, b_sh), out_shardings=(st_sh, m_sh),
                donate_argnums=0,
            )
            loss = float("nan")
            while int(state["step"]) < args.steps:
                t0 = time.perf_counter()
                step = int(state["step"])
                batch, _ = pipe.batch_at({"data_step": int(state["data"]["data_step"]), "seed": args.seed})
                batch = jax.tree_util.tree_map(jax.device_put, batch, b_sh)
                state, metrics = jstep(state, batch)
                step += 1
                loss = float(metrics["loss"])
                step_s = time.perf_counter() - t0
                if args.log_every and step % args.log_every == 0:
                    logger.info("step %d loss %.4f lr %.2e", step, loss, float(metrics["lr"]))
                preempting = notice.imminent() or schedule.should_preempt(step)
                publish_s = 0.0
                if step % args.publish_every == 0 or preempting or step >= args.steps:
                    t0 = time.perf_counter()
                    dhp.publish(job_id, "ckpt", state, step=step)
                    publish_s = time.perf_counter() - t0
                if history is not None:
                    history.append({"step": step, "loss": loss, "step_s": step_s,
                                    "publish_s": publish_s})
                if preempting and step < args.steps:
                    dhp.flush()
                    store.release(job_id)
                    notice.clear()
                    raise Preempted(f"instance reclaimed at step {step}")
            dhp.publish(
                job_id, "finished",
                product={"final_loss": loss, "steps": int(state["step"])},
                step=int(state["step"]),
            )
            return loss

        return worker

    return make_worker


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--publish-every", type=int, default=10)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--mesh", default="1x1", help="e.g. 4x2 = data×model")
    ap.add_argument(
        "--remesh", default=None,
        help="comma-separated mesh per incarnation (elastic restart), e.g. 4x2,2x2",
    )
    ap.add_argument("--preempt-at", default="", help="simulated reclaim steps, e.g. 17,29")
    ap.add_argument("--store", default="/tmp/navp-jobs")
    ap.add_argument("--job-id", default=None)
    ap.add_argument("--peak-lr", type=float, default=3e-3)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-delta", action="store_true")
    ap.add_argument("--async-publish", action="store_true")
    ap.add_argument("--log-every", type=int, default=5)
    return ap


def run_job(args, cfg, *, history: list | None = None) -> tuple[float, str]:
    """Create (or take ``--job-id``) one job and drive it to "finished"
    through the Fig.-7 loop; returns ``(final loss, job id)``."""
    store = JobStore(args.store)
    nbs = NBS(args.store + "/nbs")
    job_id = args.job_id
    if job_id is None:
        job_id = store.create_job(
            {"arch": args.arch, "steps": args.steps, "seq_len": args.seq_len, "batch": args.batch}
        ).job_id
    schedule = SpotSchedule(
        preempt_steps=tuple(int(x) for x in args.preempt_at.split(",") if x),
    )
    notice = PreemptionNotice()
    notice.install_sigterm()
    mesh_specs = (args.remesh or args.mesh).split(",")
    make_worker = build_worker(args, cfg, store, nbs, schedule, notice, job_id, mesh_specs,
                               history)
    loss, incarnations = run_preemptible(make_worker)
    logger.info(
        "job %s finished: loss=%.4f after %d incarnation(s); jobs=%s",
        job_id, loss, incarnations, store.svc_list_jobs(),
    )
    return loss, job_id


def main(argv=None) -> float:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    return run_job(args, cfg)[0]


if __name__ == "__main__":
    main()
