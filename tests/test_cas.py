"""Content-addressed store (manifest v4): identity, dedup, GC, crash safety.

The digest is the chunk identity end-to-end: a v4 manifest is a list of
digest references into ``<store_root>/objects/`` and the store only ever
writes digests it does not already hold. These tests pin the acceptance
bar from the CAS refactor: O(changed) publish bytes (a 25 %-changed delta
writes <= 35 % of a full publish), mark-and-sweep GC that never touches a
referenced object, and a kill at any point of the publish protocol leaving
the store fsck-clean with the previous CMI intact.
"""

import numpy as np
import pytest

from repro.chaos import faults
from repro.chaos.faults import FaultInjected
from repro.checkpoint.cas import ObjectStore, is_object_ref, referenced_digests
from repro.checkpoint.fsck import fsck_store
from repro.checkpoint.fsck import main as fsck_main
from repro.checkpoint.serializer import (
    SaveOptions,
    load_checkpoint,
    load_manifest,
    save_checkpoint,
)

CHUNK = 8192  # 8 KiB chunks -> one float64 row of 1024 per chunk


def _state(rng, rows=32):
    return {"w": rng.standard_normal((rows, 1024)), "step": 7}


def _assert_trees_equal(a, b):
    assert a["step"] == b["step"]
    assert a["w"].tobytes() == b["w"].tobytes()


def test_cas_roundtrip_v4(tmp_path):
    tree = _state(np.random.default_rng(0))
    man = save_checkpoint(tmp_path, "ck-a", tree, step=1,
                          options=SaveOptions(chunk_bytes=CHUNK, cas=True))
    assert man.version == 4
    assert man.data_files == []
    chunks = [c for a in man.arrays.values() for c in a.chunks]
    assert chunks and all(is_object_ref(c.ref) for c in chunks)
    assert all(c.file == c.hash and c.offset == 0 for c in chunks)
    # every referenced digest is a linked object with exactly nbytes on disk
    store = ObjectStore(tmp_path)
    for c in chunks:
        assert store.path(c.file).stat().st_size == c.nbytes
    got, _ = load_checkpoint(tmp_path, "ck-a")
    _assert_trees_equal(got, tree)
    report = fsck_store(tmp_path)
    assert report.clean and not report.orphans, report.summary()


def test_identical_resave_writes_zero_bytes(tmp_path):
    tree = _state(np.random.default_rng(1))
    opts = SaveOptions(chunk_bytes=CHUNK, cas=True)
    first = save_checkpoint(tmp_path, "ck-a", tree, options=opts)
    assert first.extra["stats"]["objects_written"] > 0
    second = save_checkpoint(tmp_path, "ck-b", tree, options=opts)
    # same bytes, different CMI name: the store already holds every digest
    assert second.extra["stats"]["objects_written"] == 0
    assert second.extra["stats"]["written_bytes"] == 0
    assert ObjectStore(tmp_path).digests() == sorted(referenced_digests(first))
    got, _ = load_checkpoint(tmp_path, "ck-b")
    _assert_trees_equal(got, tree)


def test_delta_publish_writes_at_most_35_percent(tmp_path):
    """Acceptance: 25 % of chunks changed -> delta writes <= 35 % of full."""
    rng = np.random.default_rng(2)
    tree = _state(rng)
    opts = SaveOptions(chunk_bytes=CHUNK, cas=True)
    full = save_checkpoint(tmp_path, "stage-0", tree, options=opts)
    full_bytes = full.extra["stats"]["written_bytes"]
    assert full_bytes > 0

    w = tree["w"].copy()
    changed = max(1, w.shape[0] // 4)  # 25 % of the chunk grid
    w[:changed] = rng.standard_normal((changed, w.shape[1]))
    delta = save_checkpoint(
        tmp_path, "stage-1", {"w": w, "step": 8},
        options=SaveOptions(chunk_bytes=CHUNK, cas=True, parent="stage-0"),
    )
    stats = delta.extra["stats"]
    assert stats["ref_chunks"] == w.shape[0] - changed
    assert stats["written_bytes"] <= 0.35 * full_bytes, (
        f"delta wrote {stats['written_bytes']} of {full_bytes} full bytes "
        f"({stats['written_bytes'] / full_bytes:.0%}) — CAS delta broken"
    )
    got, _ = load_checkpoint(tmp_path, "stage-1")
    assert got["w"].tobytes() == w.tobytes()


def test_v3_parent_disables_delta_chaining_but_still_loads(tmp_path):
    """A v3 parent's chunks live in stripe files, not the object tree, so a
    CAS child must not mint digest refs against it — full enumeration."""
    tree = _state(np.random.default_rng(3))
    save_checkpoint(tmp_path, "old", tree,
                    options=SaveOptions(chunk_bytes=CHUNK, writers=2))
    assert load_manifest(tmp_path, "old").version == 3
    child = save_checkpoint(
        tmp_path, "new", tree,
        options=SaveOptions(chunk_bytes=CHUNK, cas=True, parent="old"),
    )
    assert child.version == 4
    assert child.extra["stats"]["ref_chunks"] == 0  # no v3 baseline refs
    assert child.extra["stats"]["objects_written"] > 0
    assert fsck_store(tmp_path).clean
    got, _ = load_checkpoint(tmp_path, "new")
    _assert_trees_equal(got, tree)


def test_gc_sweep_never_touches_referenced_objects(tmp_path):
    import shutil

    rng = np.random.default_rng(4)
    tree = _state(rng)
    opts = SaveOptions(chunk_bytes=CHUNK, cas=True)
    save_checkpoint(tmp_path, "ck-dead", tree, options=opts)
    w = tree["w"].copy()
    w[:8] = rng.standard_normal((8, 1024))
    keep_man = save_checkpoint(
        tmp_path, "ck-live", {"w": w, "step": 9},
        options=SaveOptions(chunk_bytes=CHUNK, cas=True, parent="ck-dead"),
    )
    shutil.rmtree(tmp_path / "ck-dead")  # drop the manifest root

    store = ObjectStore(tmp_path)
    before = set(store.digests())
    marked = referenced_digests(keep_man)
    with store.sweep_guard():
        removed = store.sweep(marked)
    assert set(removed) == before - marked  # exactly the unreferenced ones
    assert set(store.digests()) == marked
    got, _ = load_checkpoint(tmp_path, "ck-live")
    assert got["w"].tobytes() == w.tobytes()
    report = fsck_store(tmp_path)
    assert report.clean and not report.orphans, report.summary()


@pytest.mark.parametrize("point,after", [
    ("cas.publish.pre_link", 2),  # third object write, mid-delta
    ("cas.publish.post_objects", 0),  # fires once: objects durable, no manifest
])
def test_crash_mid_publish_leaves_fsck_clean_and_parent_intact(tmp_path, point, after):
    """A failure at either publish fault point must never commit a manifest
    with dangling refs; the previous CMI keeps loading bit-identically and
    a retry converges (deduping against whatever objects survived)."""
    rng = np.random.default_rng(5)
    tree = _state(rng)
    opts = SaveOptions(chunk_bytes=CHUNK, cas=True)
    save_checkpoint(tmp_path, "ck-0", tree, options=opts)

    w = tree["w"].copy()
    w[:8] = rng.standard_normal((8, 1024))
    next_tree = {"w": w, "step": 8}
    with faults.arm({"point": point, "action": "error", "after": after}):
        with pytest.raises(FaultInjected):
            save_checkpoint(tmp_path, "ck-1", next_tree,
                            options=SaveOptions(chunk_bytes=CHUNK, cas=True,
                                                parent="ck-0"))
    assert not (tmp_path / "ck-1").exists()  # no torn CMI dir
    report = fsck_store(tmp_path)
    assert report.clean, report.summary()  # orphans at worst, never errors
    got, _ = load_checkpoint(tmp_path, "ck-0")
    _assert_trees_equal(got, tree)

    # retry (the respawned worker's resume) completes and loads clean
    save_checkpoint(tmp_path, "ck-1", next_tree,
                    options=SaveOptions(chunk_bytes=CHUNK, cas=True,
                                        parent="ck-0"))
    got, _ = load_checkpoint(tmp_path, "ck-1")
    assert got["w"].tobytes() == w.tobytes()
    assert fsck_store(tmp_path).clean


def test_fsck_flags_corrupt_object_and_dangling_ref(tmp_path):
    tree = _state(np.random.default_rng(6), rows=4)
    man = save_checkpoint(tmp_path, "ck-a", tree,
                          options=SaveOptions(chunk_bytes=CHUNK, cas=True))
    store = ObjectStore(tmp_path)
    digests = sorted(referenced_digests(man))

    # flip one byte of one object: digest re-hash AND chunk CRC must trip
    victim = store.path(digests[0])
    blob = bytearray(victim.read_bytes())
    blob[0] ^= 0xFF
    victim.write_bytes(bytes(blob))
    report = fsck_store(tmp_path)
    assert not report.clean
    assert any("digest" in e or "crc" in e for e in report.errors), report.errors
    assert fsck_main([str(tmp_path), "-q"]) == 2
    victim.write_bytes(bytes(b ^ (0xFF if i == 0 else 0)
                             for i, b in enumerate(blob)))  # restore

    # delete an object out from under the manifest: dangling ref
    store.path(digests[1]).unlink()
    report = fsck_store(tmp_path)
    assert any("dangling" in e or "missing" in e for e in report.errors), report.errors
    assert fsck_main([str(tmp_path), "-q"]) == 2


def test_fsck_strict_flags_orphans(tmp_path):
    tree = _state(np.random.default_rng(7), rows=4)
    man = save_checkpoint(tmp_path, "ck-a", tree,
                          options=SaveOptions(chunk_bytes=CHUNK, cas=True))
    from repro.utils import content_hash

    store = ObjectStore(tmp_path)
    # an unreferenced object (killed publisher whose manifest never landed);
    # content-named, so its bytes re-hash clean — orphaned, not corrupt
    blob = b"orphaned bytes"
    orphan = content_hash(blob)
    store.put(orphan, blob)
    store.fsync_buckets([orphan])
    report = fsck_store(tmp_path)
    assert report.clean and len(report.orphans) == 1
    assert fsck_main([str(tmp_path), "-q"]) == 0  # benign by default
    assert fsck_main([str(tmp_path), "-q", "--strict"]) == 2

    # GC reclaims it and strict goes green again
    with store.sweep_guard():
        removed = store.sweep(referenced_digests(man))
    assert removed == [orphan]
    assert fsck_main([str(tmp_path), "-q", "--strict"]) == 0


def test_concurrent_sweeps_reclaim_every_detached_byte(tmp_path):
    """Many threads detach into their own stores while the one reclaimer
    unlinks: every file is reclaimed once and the backlog returns to 0."""
    import sys
    import threading

    from repro.checkpoint.cas import RECLAIMER
    from repro.utils import content_hash

    def worker(k, out):
        store = ObjectStore(tmp_path / f"s{k}")
        for r in range(5):
            blobs = [f"{k}-{r}-{i}".encode() * 64 for i in range(6)]
            digests = [content_hash(b) for b in blobs]
            with store.publish_guard():
                for d, b in zip(digests, blobs):
                    store.put(d, b)
            with store.sweep_guard():
                out[k] += len(store.sweep(set(digests[:2])))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        out = [0] * 8
        threads = [threading.Thread(target=worker, args=(k, out)) for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert RECLAIMER.drain(timeout=60)
    # each round keeps its first 2 objects: 4 of this round + the previous round's 2 go
    assert out == [4 + 4 * 6] * 8
    assert RECLAIMER.backlog_bytes == 0
    for k in range(8):
        assert list((tmp_path / f"s{k}" / ".reclaim").iterdir()) == []
        assert len(ObjectStore(tmp_path / f"s{k}").digests()) == 2
