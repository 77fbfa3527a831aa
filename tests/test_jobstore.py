"""Job store: the paper's status machine + three services (§3.3, Fig. 5/6)."""

import numpy as np
import pytest

from repro.checkpoint import SaveOptions, save_checkpoint
from repro.core.jobstore import STATUS_CKPT, STATUS_FINISHED, STATUS_NEW, JobStore


def test_status_machine_fig5(tmp_path):
    store = JobStore(tmp_path)
    j1 = store.create_job({"k": 1})
    j2 = store.create_job({"k": 2})
    j3 = store.create_job({"k": 3})
    # publish j2 as ckpt, j3 as finished
    save_checkpoint(store.cmi_root(j2.job_id), "cmi-a", {"x": np.ones(3)})
    store.svc_publish_job(j2.job_id, STATUS_CKPT, cmi="cmi-a", step=5)
    store.svc_publish_job(j3.job_id, STATUS_FINISHED, product=None)
    assert store.svc_list_jobs() == [["1", "new"], ["2", "ckpt"], ["3", "finished"]]


def test_get_job_claims_next_unfinished(tmp_path):
    store = JobStore(tmp_path)
    store.create_job({})
    store.create_job({})
    a = store.svc_get_job(worker="w1")
    b = store.svc_get_job(worker="w2")
    assert a.job_id != b.job_id  # leases prevent double-claim
    assert store.svc_get_job(worker="w3") is None
    store.release(a.job_id)
    c = store.svc_get_job(worker="w3")
    assert c.job_id == a.job_id


def test_publish_requires_committed_cmi(tmp_path):
    store = JobStore(tmp_path)
    j = store.create_job({})
    with pytest.raises(ValueError):
        store.svc_publish_job(j.job_id, STATUS_CKPT, cmi="nope")


def test_publish_finished_is_terminal(tmp_path):
    store = JobStore(tmp_path)
    j = store.create_job({})
    store.svc_publish_job(j.job_id, STATUS_FINISHED)
    with pytest.raises(ValueError):
        store.svc_publish_job(j.job_id, STATUS_FINISHED)


def test_gc_keeps_delta_ancestors(tmp_path):
    store = JobStore(tmp_path)
    j = store.create_job({})
    root = store.cmi_root(j.job_id)
    w = np.zeros((16, 4), np.float32)
    names = []
    parent = None
    for i in range(4):
        w = w.copy(); w[i] += 1
        name = f"cmi-{i:04d}"
        save_checkpoint(root, name, {"w": w}, options=SaveOptions(chunk_bytes=64, parent=parent))
        store.svc_publish_job(j.job_id, STATUS_CKPT, cmi=name, step=i, keep_last=2)
        names.append(name)
        parent = name
    kept = store.list_cmis(j.job_id)
    # last two kept, plus every chain ancestor their chunks reference
    assert names[-1] in kept and names[-2] in kept
    assert "cmi-0000" in kept  # ancestor still referenced through the chain
    # restoring the latest still works after GC
    from repro.checkpoint import load_checkpoint

    got, _ = load_checkpoint(root, names[-1])
    np.testing.assert_array_equal(got["w"], w)


def test_interrupted_job_without_cmi_returns_to_new(tmp_path):
    store = JobStore(tmp_path)
    j = store.create_job({})
    store.svc_get_job(j.job_id, worker="w")
    job = store.release(j.job_id, to_status=STATUS_NEW)
    assert job.status == STATUS_NEW and not job.leased()


# ---------------------------------------------------------------------------
# lease heartbeats + expired-lease stealing (ROADMAP item c)
# ---------------------------------------------------------------------------


def test_renew_lease_extends_and_guards_owner(tmp_path):
    import time

    from repro.core.jobstore import LeaseLost

    store = JobStore(tmp_path)
    j = store.create_job({})
    store.svc_get_job(j.job_id, worker="w1", lease_s=0.5)
    store.renew_lease(j.job_id, "w1", lease_s=60.0)  # heartbeat
    assert store.read_job(j.job_id).lease_expiry > time.time() + 30
    with pytest.raises(LeaseLost):
        store.renew_lease(j.job_id, "rival", lease_s=60.0)
    # renewals do not spam history (heartbeat cadence would dominate it)
    events = [h["event"] for h in store.read_job(j.job_id).history]
    assert events == ["leased:w1"]


def test_two_claimants_expired_lease_is_stolen(tmp_path):
    """Regression for lease stealing: while w1's lease is live a polite
    (steal=False) rival gets nothing; once the lease expires without a
    heartbeat the rival claims the job without any explicit release."""
    import time

    store = JobStore(tmp_path)
    j = store.create_job({})
    won = store.svc_get_job(j.job_id, worker="w1", lease_s=0.4, steal=False)
    assert won.lease_owner == "w1"
    # live lease: the rival is refused
    assert store.svc_get_job(j.job_id, worker="w2", steal=False) is None
    assert store.svc_get_job(worker="w2") is None  # claim-next also refuses
    # w1 stalls (no heartbeat) -> lease expires -> rival takes over
    time.sleep(0.5)
    stolen = store.svc_get_job(j.job_id, worker="w2", steal=False)
    assert stolen is not None and stolen.lease_owner == "w2"
    # the stalled worker's next heartbeat must fail loudly
    from repro.core.jobstore import LeaseLost

    with pytest.raises(LeaseLost):
        store.renew_lease(j.job_id, "w1")


def test_heartbeat_thread_keeps_lease_alive(tmp_path):
    """A slow-but-healthy worker heartbeating at lease_s/3 never loses its
    job, even when each 'step' takes longer than the lease."""
    import time

    from repro.fabric.worker import start_lease_heartbeat

    store = JobStore(tmp_path)
    j = store.create_job({})
    store.svc_get_job(j.job_id, worker="w1", lease_s=0.6)
    stop = start_lease_heartbeat(store, j.job_id, "w1", lease_s=0.6)
    try:
        deadline = time.time() + 1.5  # >2 lease lifetimes
        while time.time() < deadline:
            assert store.svc_get_job(j.job_id, worker="rival", steal=False) is None
            time.sleep(0.1)
    finally:
        stop.set()


# ---------------------------------------------------------------------------
# keep_last sweep: detach under the store lock, reclaim on a background thread
# ---------------------------------------------------------------------------

CAS_CHUNK = 4096  # one float64 row of 512 per chunk


def _cas_publish(store, job_id, name, w, parent=None, step=0):
    save_checkpoint(store.cmi_root(job_id), name, {"w": w},
                    options=SaveOptions(chunk_bytes=CAS_CHUNK, cas=True, parent=parent))
    store.svc_publish_job(job_id, STATUS_CKPT, cmi=name, step=step, keep_last=2)


def _kept_refs(root):
    from repro.checkpoint.atomic import list_committed
    from repro.checkpoint.cas import referenced_digests
    from repro.checkpoint.serializer import load_manifest

    out = set()
    for p in list_committed(root):
        out |= referenced_digests(load_manifest(root, p.name))
    return out


def _publish_series(store, job_id, states):
    parent = None
    for i, w in enumerate(states):
        name = f"cmi-{i:04d}"
        _cas_publish(store, job_id, name, w, parent=parent, step=i)
        parent = name
    return parent


def test_sweep_leaves_exactly_the_kept_objects_without_a_drain(tmp_path, held_reclaimer):
    from repro.checkpoint.cas import ObjectStore

    store = JobStore(tmp_path)
    j = store.create_job({}).job_id
    rng = np.random.default_rng(11)
    _publish_series(store, j, [rng.standard_normal((8, 512)) for _ in range(4)])
    root = store.cmi_root(j)
    # the reclaimer is held: nothing detached has been unlinked yet
    assert set(ObjectStore(root).digests()) == _kept_refs(root)
    assert len(list((root / ".reclaim").iterdir())) == 2 * 8


def test_drain_reclaim_empties_the_reclaim_dir(tmp_path):
    from repro.checkpoint.cas import RECLAIMER

    store = JobStore(tmp_path)
    j = store.create_job({}).job_id
    rng = np.random.default_rng(12)
    _publish_series(store, j, [rng.standard_normal((8, 512)) for _ in range(4)])
    assert store.drain_reclaim(timeout=60)
    assert list((store.cmi_root(j) / ".reclaim").iterdir()) == []
    assert RECLAIMER.backlog_bytes == 0


def test_reclaim_dir_left_by_a_killed_process_is_emptied_by_the_next_sweep(tmp_path):
    from repro.checkpoint.cas import ObjectStore
    from repro.checkpoint.fsck import fsck_store
    from repro.checkpoint.fsck import main as fsck_main
    from repro.utils import content_hash

    store = JobStore(tmp_path)
    j = store.create_job({}).job_id
    rng = np.random.default_rng(13)
    last = _publish_series(store, j, [rng.standard_normal((8, 512)) for _ in range(3)])
    assert store.drain_reclaim(timeout=60)
    root = store.cmi_root(j)
    # a process killed between detach and reclaim leaves its files behind
    for k in range(3):
        (root / ".reclaim" / f"{k:064x}.4242.{k}").write_bytes(b"x" * CAS_CHUNK)
    report = fsck_store(root)
    assert report.clean and not report.orphans, report.summary()
    assert fsck_main([str(root), "-q", "--strict"]) == 0
    # ... and a killed publisher an object no manifest names, and a tmp file
    objects = ObjectStore(root)
    orphan = content_hash(b"orphaned")
    objects.put(orphan, b"orphaned")
    (objects.path(orphan).parent / ".tmp-0123-4242-1").write_bytes(b"torn")
    assert len(fsck_store(root).orphans) == 2
    # the next process's JobStore: its first GC of the job walks the tree
    again = JobStore(tmp_path)
    _cas_publish(again, j, "cmi-0009", rng.standard_normal((8, 512)), parent=last, step=9)
    assert again.drain_reclaim(timeout=60)
    assert list((root / ".reclaim").iterdir()) == []
    assert not objects.has(orphan) and not objects.tmp_files()
    assert fsck_main([str(root), "-q", "--strict"]) == 0


def test_digest_detached_twice_before_reclaim_never_collides(tmp_path, held_reclaimer):
    from repro.checkpoint import load_checkpoint
    from repro.checkpoint.cas import ObjectStore, referenced_digests
    from repro.checkpoint.fsck import main as fsck_main
    from repro.checkpoint.serializer import load_manifest

    store = JobStore(tmp_path)
    j = store.create_job({}).job_id
    root = store.cmi_root(j)
    rng = np.random.default_rng(14)
    a, b, c = (rng.standard_normal((8, 512)) for _ in range(3))
    _cas_publish(store, j, "cmi-0000", a)
    a_digests = referenced_digests(load_manifest(root, "cmi-0000"))
    # A's objects: detached by the third publish, written again by the
    # fourth, detached again by the sixth, the reclaimer held throughout
    for i, w in enumerate([b, c, a, b, c], start=1):
        _cas_publish(store, j, f"cmi-{i:04d}", w, parent=f"cmi-{i - 1:04d}", step=i)
    names = [f.name for f in (root / ".reclaim").iterdir()]
    assert len(a_digests) == 8 and len(names) == 4 * 8
    assert all(sum(n.split(".")[0] == d for n in names) == 2 for d in a_digests)
    assert set(ObjectStore(root).digests()) == _kept_refs(root)
    got, _ = load_checkpoint(root, "cmi-0005")
    assert got["w"].tobytes() == c.tobytes()
    held_reclaimer.set()
    assert store.drain_reclaim(timeout=60)
    assert list((root / ".reclaim").iterdir()) == []
    assert fsck_main([str(root), "-q", "--strict"]) == 0
