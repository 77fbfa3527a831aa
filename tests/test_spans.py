"""The program's span recorder, and the spans the publish, restart, train
step and serve admit leave in it."""

import threading
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.core import DHP, NBS, JobStore
from repro.core.jobstore import STATUS_CKPT
from repro.spans import annotate, span

CKPT_PHASES = {"ckpt.d2h", "ckpt.hash_wait", "ckpt.write_wait", "ckpt.commit", "jobstore.record"}


def since(t0: float) -> list:
    return spans.records(t0)


def children(recs: list, parent) -> list:
    return [r for r in recs if r.parent == parent.id]


def covered(recs: list) -> float:
    """Seconds of the union of the records' intervals."""
    total, end = 0.0, float("-inf")
    for r in sorted(recs, key=lambda r: r.start):
        if r.end > end:
            total += r.end - max(r.start, end)
            end = r.end
    return total


def test_parents_follow_nesting_on_each_thread():
    t0 = time.perf_counter()
    inner = {}

    def other():
        with span("t.other") as o:
            with span("t.other_child") as oc:
                inner["o"], inner["oc"] = o, oc

    with span("t.outer") as outer:
        with span("t.mid") as mid:
            th = threading.Thread(target=other)
            th.start()
            th.join(timeout=30)
            assert not th.is_alive()
            with span("t.leaf") as leaf:
                pass
    assert outer.parent is None
    assert mid.parent == outer.id and leaf.parent == mid.id
    # a span opened on another thread does not inherit this thread's stack
    assert inner["o"].parent is None and inner["oc"].parent == inner["o"].id
    assert inner["o"].thread != outer.thread
    got = {r.name for r in since(t0)}
    assert {"t.outer", "t.mid", "t.leaf", "t.other", "t.other_child"} <= got
    assert outer.start <= mid.start <= leaf.start <= leaf.end <= mid.end <= outer.end
    assert spans.last() is outer


def test_annotate_lands_on_the_innermost_open_span():
    with span("t.a", job="j") as a:
        annotate(n=1)
        with span("t.b") as b:
            annotate(bytes=7, written_bytes=0)
        annotate(m=2)
    assert a.attrs == {"job": "j", "n": 1, "m": 2}
    assert b.attrs == {"bytes": 7, "written_bytes": 0}
    annotate(ignored=1)  # no span open: nothing to add to, no error


def test_records_hold_only_spans_wholly_inside_the_window():
    with span("t.before"):
        pass
    t0 = time.perf_counter()
    with span("t.straddle"):
        with span("t.inside"):
            pass
        t1 = time.perf_counter()
        with span("t.after"):
            pass
    names = [r.name for r in spans.records(t0, t1)]
    assert "t.inside" in names
    assert not {"t.before", "t.straddle", "t.after"} & set(names)


def test_ring_is_bounded_and_says_when_it_dropped(monkeypatch):
    monkeypatch.setattr(spans, "_ring", deque(maxlen=4))
    monkeypatch.setattr(spans, "_dropped_start", float("-inf"))
    t0 = time.perf_counter()
    for i in range(4):
        with span(f"t.r{i}"):
            pass
    assert len(spans.records()) == 4 and not spans.truncated(t0)
    with span("t.r4"):
        pass
    # t.r0 fell out of the ring: a window from t0 on has lost a span
    assert [r.name for r in spans.records()] == ["t.r1", "t.r2", "t.r3", "t.r4"]
    assert spans.truncated(t0)
    assert not spans.truncated(time.perf_counter())


def test_compile_is_recorded_from_jax_monitoring():
    t0 = time.perf_counter()
    with span("t.compiling") as outer:
        jax.jit(lambda x: jnp.sin(x) * 3.0 + t0)(jnp.ones(5)).block_until_ready()
    comp = [r for r in since(t0) if r.name == spans.COMPILE]
    phases = {r.attrs["phase"] for r in comp}
    assert "backend_compile" in phases and "jaxpr_trace" in phases
    assert all(r.parent == outer.id and r.end >= r.start >= t0 for r in comp)
    assert covered(comp) <= outer.duration


def test_spans_land_in_the_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with span("dhp.publish", job="job-7", step=3, status="ckpt"):
            with span("ckpt.d2h"):
                np.asarray(jnp.arange(8.0))
    jax.profiler.stop_trace()
    (pb,) = tmp_path.rglob("*.xplane.pb")
    ev = {}
    for plane in ProfileData.from_file(str(pb)).planes:
        for line in plane.lines:
            for e in line.events:
                ev.setdefault(e.name, (e.start_ns, e.start_ns + e.duration_ns))
    win, pub, d2h = ev["bench.window"], ev["dhp.publish"], ev["ckpt.d2h"]
    assert win[0] <= pub[0] <= d2h[0] <= d2h[1] <= pub[1] <= win[1]


@pytest.fixture
def dhp(tmp_path):
    nbs = NBS(tmp_path / "s3")
    nbs.add_node("A", mesh=None)
    store = JobStore(tmp_path / "jobs")
    return DHP(nbs, "A", store, chunk_bytes=1 << 10), store


def small_state(k: float = 0.0) -> dict:
    return {"w": jnp.arange(4096, dtype=jnp.float32) + k, "b": jnp.ones((64, 8)),
            "step": jnp.asarray(3)}


def publish(dhp, job_id, state, step):
    t0 = time.perf_counter()
    name = dhp.publish(job_id, STATUS_CKPT, state, step=step)
    (pub,) = [r for r in since(t0) if r.name == "dhp.publish" and r.attrs["job"] == job_id]
    return name, pub, since(t0)


def test_publish_splits_into_its_phases(dhp):
    dhp, store = dhp
    job = store.create_job({}).job_id
    name, pub, recs = publish(dhp, job, small_state(), 3)
    assert pub.attrs["step"] == 3 and pub.attrs["status"] == STATUS_CKPT
    kids = children(recs, pub)
    assert CKPT_PHASES <= {r.name for r in kids}
    phases = [r for r in kids if r.name in CKPT_PHASES]
    assert all(pub.start <= r.start <= r.end <= pub.end for r in phases)
    assert sum(r.duration for r in phases) <= pub.duration
    assert spans.last() is pub


def test_publish_counters_are_the_manifest_stats(dhp):
    from repro.checkpoint.serializer import load_manifest

    dhp, store = dhp
    job = store.create_job({}).job_id
    name, pub, _ = publish(dhp, job, small_state(), 1)
    stats = load_manifest(store.cmi_root(job), name).extra["stats"]
    assert {k: pub.attrs[k] for k in stats} == stats
    assert stats["bytes"] == 4096 * 4 + 64 * 8 * 4 + 4
    assert 0 < stats["written_bytes"] <= stats["bytes"]


def test_identical_republish_writes_nothing(dhp):
    dhp, store = dhp
    job = store.create_job({}).job_id
    state = small_state(1.0)
    publish(dhp, job, state, 1)
    _, again, _ = publish(dhp, job, state, 2)
    assert again.attrs["written_bytes"] == 0 and again.attrs["objects_written"] == 0
    assert again.attrs["ref_bytes"] == again.attrs["bytes"] > 0


def test_restart_is_recorded(dhp):
    dhp, store = dhp
    job = store.create_job({}).job_id
    publish(dhp, job, small_state(), 5)
    t0 = time.perf_counter()
    state, step = dhp.restart(job)
    assert step == 5
    (rst,) = [r for r in since(t0) if r.name == "dhp.restart"]
    assert rst.attrs["job"] == job and rst.duration > 0


def test_history_times_are_the_spans_durations(tmp_path):
    from repro.configs import get_smoke_config
    from repro.launch import train as T

    args = T.build_parser().parse_args([
        "--arch", "qwen3-1.7b", "--smoke", "--steps", "3", "--publish-every", "2",
        "--seq-len", "16", "--batch", "2", "--store", str(tmp_path / "jobs"),
        "--log-every", "0",
    ])
    history: list = []
    t0 = time.perf_counter()
    _, job = T.run_job(args, get_smoke_config("qwen3-1.7b"), history=history)
    recs = since(t0)
    steps = [r for r in recs if r.name == "train.step" and r.attrs["job"] == job]
    pubs = [r for r in recs if r.name == "dhp.publish" and r.attrs["status"] == STATUS_CKPT]
    assert [h["step_s"] for h in history] == [r.duration for r in steps]
    assert [h["publish_s"] for h in history if h["publish_s"]] == [r.duration for r in pubs]
    assert [h["step"] for h in history if h["publish_s"]] == [2, 3]


def test_admit_prefill_s_is_the_spans_duration():
    from repro.serve.engine import ToyEngine
    from repro.serve.worker import ServeHost

    host = ServeHost(ToyEngine(d=16, vocab=64))
    t0 = time.perf_counter()
    res = host.admit("r1", [1, 2, 3], 4)
    host.step()
    recs = since(t0)
    (pre,) = [r for r in recs if r.name == "engine.prefill"]
    assert res["prefill_s"] == pre.duration
    assert [r.name for r in recs].count("engine.decode") == 1


def test_sweep_and_reclaim_spans_count_the_detached_objects(dhp):
    dhp, store = dhp
    job = store.create_job({}).job_id
    for k in range(2):
        publish(dhp, job, small_state(float(k)), k)
    assert store.drain_reclaim(timeout=60)  # no earlier background work lands in ours
    _, pub, recs = publish(dhp, job, small_state(2.0), 2)  # drops the first CMI's objects
    assert store.drain_reclaim(timeout=60)
    (rec,) = [r for r in children(recs, pub) if r.name == "jobstore.record"]
    assert rec.attrs["reclaim_backlog_bytes"] == 0
    (sweep,) = [r for r in since(rec.start) if r.name == "jobstore.sweep"]
    assert sweep.parent == rec.id and sweep.attrs["walk"] is False
    assert sweep.attrs["detached_objects"] > 0 and sweep.attrs["detached_bytes"] > 0
    (reclaim,) = [r for r in since(sweep.start) if r.name == "jobstore.reclaim"]
    assert reclaim.thread != sweep.thread and reclaim.parent is None
    assert reclaim.attrs == {"objects": sweep.attrs["detached_objects"],
                             "bytes": sweep.attrs["detached_bytes"]}


def test_flush_and_restart_do_not_wait_for_the_reclaimer(dhp, held_reclaimer):
    dhp, store = dhp
    job = store.create_job({}).job_id
    for k in range(4):
        publish(dhp, job, small_state(float(k)), k)
    dhp.flush(timeout=30)
    state, step = dhp.restart(job)
    assert step == 3 and np.array_equal(np.asarray(state["w"]), np.asarray(small_state(3.0)["w"]))
    # every detached object is still waiting for the held reclaimer
    assert list((store.cmi_root(job) / ".reclaim").iterdir())
    _, pub, recs = publish(dhp, job, small_state(4.0), 4)
    (rec,) = [r for r in children(recs, pub) if r.name == "jobstore.record"]
    assert rec.attrs["reclaim_backlog_bytes"] > 0
    held_reclaimer.set()
    assert store.drain_reclaim(timeout=60)
