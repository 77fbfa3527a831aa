"""End-to-end behaviour tests for the paper's system.

The headline scenario: a science-data job (satellite co-location) and an ML
training job both survive spot-instance preemption via application-initiated
checkpointing, resume on different "instances", and publish products — the
paper's Fig. 7/8 flow on real computations.
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import DHP, NBS, JobStore
from repro.core import colocation as co
from repro.core.dhp import Preempted
from repro.core.itinerary import Itinerary, Stage
from repro.core.jobstore import STATUS_CKPT, STATUS_FINISHED
from repro.core.preemption import run_preemptible


def test_colocation_job_survives_preemption(tmp_path):
    """Fig. 7: publish("ckpt") between stages; kill after stage 3 published;
    a fresh worker restarts from the CMI and finishes the product."""
    nbs = NBS(tmp_path / "s3")
    nbs.add_node("cloud-0", mesh=None)
    nbs.add_node("cloud-1", mesh=None)
    store = JobStore(tmp_path / "jobs")
    job = store.create_job({"app": "viirs-cris"})

    def stage_read(s):
        g = co.make_synthetic_granules(0, n_scans=2, viirs_pixels_per_scan=200, viirs_lines_per_scan=2)
        return {**s, **{k: jnp.asarray(v) for k, v in g.items()}}

    def stage_geometry(s):
        los = co.cris_los_ecef(s["cris_lat"], s["cris_lon"], s["sat_pos"])
        pos = co.viirs_pos_ecef(s["viirs_lat"], s["viirs_lon"])
        return {**s, "los": los, "pos": pos}

    def stage_match(s):
        idx, cos, within = co.match_viirs_to_cris(s["pos"], s["los"], s["sat_pos"])
        return {**s, "idx": idx, "within": within}

    killed = {"done": False}

    def make_worker(incarnation):
        def worker():
            node = f"cloud-{incarnation}"
            dhp = DHP(nbs, node, store)
            it = Itinerary(dhp, job.job_id)
            stages = [
                Stage(node, stage_read, "read", publish=True),
                Stage(node, stage_geometry, "geom", publish=True),
                Stage(node, stage_match, "match", publish=True),
            ]
            j = store.read_job(job.job_id)
            if j.status == STATUS_CKPT:
                s = it.resume(stages)
            else:
                s = it.run({}, stages)
                if not killed["done"]:
                    killed["done"] = True
                    raise Preempted("spot reclaim after match stage published")
            g = {k: np.asarray(v) for k, v in s.items() if hasattr(v, "shape")}
            prod = co.build_product(
                {"cris_lat": g["cris_lat"], "viirs_rad": g["viirs_rad"]},
                s["idx"], s["within"],
            )
            dhp.publish(job.job_id, STATUS_FINISHED, product={"matched_frac": prod["matched_frac"]})
            return prod["matched_frac"]

        return worker

    frac, incarnations = run_preemptible(make_worker)
    assert incarnations == 2
    assert frac > 0.9
    assert store.read_job(job.job_id).status == STATUS_FINISHED


def test_training_job_end_to_end(subproc, tmp_path):
    """The full launcher path (Fig. 7 loop) with one simulated reclaim."""
    out = subproc(
        f"STORE = {str(tmp_path)!r}\n" + r"""
import repro.launch.train as T
loss = T.main([
    "--arch", "hymba-1.5b", "--smoke", "--steps", "8", "--publish-every", "3",
    "--store", STORE, "--seq-len", "32", "--batch", "4",
    "--preempt-at", "4", "--log-every", "0",
])
import numpy as np
assert np.isfinite(loss)
from repro.core.jobstore import JobStore
(job_id, status), = JobStore(STORE).svc_list_jobs()
assert status == "finished", (job_id, status)
print("SYS_OK")
""",
        devices=1,
        timeout=600,
    )
    assert "SYS_OK" in out


def test_serve_driver(subproc):
    out = subproc(
        r"""
import repro.launch.serve as S
m = S.main(["--arch", "qwen3-1.7b", "--smoke", "--prompt-len", "16", "--gen", "8", "--batch", "2"])
assert len(m["transcripts"]) == 2, m["transcripts"]
assert all(len(t) == 8 for t in m["transcripts"].values()), m["transcripts"]
assert m["decode_tok_s"] > 0, m
print("SERVE_OK")
""",
        devices=1,
        timeout=600,
    )
    assert "SERVE_OK" in out
