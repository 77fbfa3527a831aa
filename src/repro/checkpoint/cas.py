"""Content-addressed object store: the blake2b digest IS the chunk identity.

Manifest v4 CMIs do not carry their own ``data-*.bin`` stripes. Every chunk
lives exactly once in a store-level object tree::

    <store_root>/objects/<digest[:2]>/<digest>

and a v4 manifest is just a list of digest references (``ChunkEntry`` with
``ref="objects/<digest[:2]>"``, ``file=<digest>``, ``offset=0``) — which
resolves through the *unchanged* restore path: ``_ChunkReader.file_path(
owner, file)`` already joins ``root/owner/file``, so a digest reference is
read exactly like a v1–v3 delta reference into a sibling CMI.

Durability protocol (paper §Q4, extended to shared objects):

1. each absent object is written to a ``.tmp-*`` file in its bucket,
   fsync'd, then atomically ``os.replace``'d to its digest name
   (``cas.publish.pre_link`` fires between fsync and link — a SIGKILL
   there leaves only an invisible tmp file, never a torn object);
2. bucket directories are fsync'd once all objects are linked, then
   ``cas.publish.post_objects`` fires — a SIGKILL there leaves fully
   durable but unreferenced objects (benign orphans, swept by GC);
3. only then does ``CommitScope`` stage + COMMIT the manifest, so a
   manifest is never visible while any object it references is missing.

Because objects are immutable and content-named, concurrent publishers
racing on the same digest are idempotent: both write distinct tmp files
with identical bytes and the second ``os.replace`` is a no-op overwrite.
Publisher/GC coordination uses the store's existing fcntl discipline: a
publisher holds a *shared* ``flock`` on ``objects/.lock`` across object
writes and the manifest commit, while the mark-and-sweep GC takes it
*exclusive* — a sweep can never detach objects a mid-commit publisher is
about to reference, and a SIGKILLed holder releases the lock with the
process.

The sweep is split in two. *Detach*, under the exclusive lock, renames
each unreferenced object out of the tree into ``<store_root>/.reclaim/`` —
a sibling of ``objects/`` on the same filesystem, which no listing of
objects, CMIs, staging dirs or fsck walks. A rename frees no blocks; when
detach returns the object tree is exactly what unlinking would have left.
The objects it considers are those of the manifests the GC just dropped,
so the publish path lists no directory of the tree. *Reclaim* — the
unlinks, whose cost is the filesystem freeing each file's blocks — runs on
one lazily started daemon thread per process (:data:`RECLAIMER`), off the
publish path and without any store lock: nothing references a detached
file. The same thread runs the sweeps that walk the whole tree, for what
no dropped manifest names (objects and ``.tmp-*`` files of killed
publishers). A process killed with files still in ``.reclaim/`` strands
only unreferenced bytes; the job's next sweep hands the directory over
again.
"""

from __future__ import annotations

import fcntl
import itertools
import os
import queue
import threading
from pathlib import Path

from repro.chaos import faults
from repro.spans import annotate, span
from repro.utils import logger

OBJECTS_DIR = "objects"
RECLAIM_DIR = ".reclaim"
_LOCK_FILE = ".lock"
_TMP_PREFIX = ".tmp-"
_detach_seq = itertools.count()


def object_ref(digest: str) -> str:
    """The ``ChunkEntry.ref`` value for a digest (the owning 'CMI' dir)."""
    return f"{OBJECTS_DIR}/{digest[:2]}"


def object_rel(digest: str) -> str:
    """Store-root-relative path of a digest's object file."""
    return f"{OBJECTS_DIR}/{digest[:2]}/{digest}"


def is_object_ref(ref: str | None) -> bool:
    """True when a chunk's ``ref`` points into the object tree (v4 chunk)."""
    return ref is not None and ref.startswith(OBJECTS_DIR + "/")


def referenced_objects(manifest) -> dict[str, int]:
    """Every object a manifest's chunk table references, with its bytes."""
    return {c.file: c.nbytes for aentry in manifest.arrays.values()
            for c in aentry.chunks if is_object_ref(c.ref)}


def referenced_digests(manifest) -> set[str]:
    """All object digests a manifest's chunk table references (GC mark set)."""
    return set(referenced_objects(manifest))


class ObjectStore:
    """Digest-addressed chunk objects under ``<root>/objects/``."""

    def __init__(self, root: str | os.PathLike):
        self.root = Path(root)
        self.dir = self.root / OBJECTS_DIR
        self.reclaim_dir = self.root / RECLAIM_DIR

    def path(self, digest: str) -> Path:
        return self.dir / digest[:2] / digest

    def has(self, digest: str) -> bool:
        return self.path(digest).exists()

    def put(self, digest: str, buf) -> int:
        """Durably write one object; returns bytes written (0 on dedup hit).

        tmp-write + fsync + atomic link (``os.replace``). Idempotent under
        concurrent publishers: content-named files make the race benign.
        The caller is responsible for :meth:`fsync_buckets` afterwards.
        """
        final = self.path(digest)
        if final.exists():
            return 0
        bucket = final.parent
        bucket.mkdir(parents=True, exist_ok=True)
        tmp = bucket / f"{_TMP_PREFIX}{digest[:16]}-{os.getpid()}-{threading.get_ident()}"
        n = buf.nbytes if isinstance(buf, memoryview) else len(buf)
        try:
            with open(tmp, "wb") as f:
                f.write(buf)
                f.flush()
                os.fsync(f.fileno())
            faults.fire("cas.publish.pre_link")
            os.replace(tmp, final)
        except BaseException:
            tmp.unlink(missing_ok=True)
            raise
        return n

    def fsync_buckets(self, digests) -> None:
        """fsync every bucket dir (and ``objects/`` itself) the digests touch,
        making the links themselves durable before the manifest commits."""
        if not self.dir.is_dir():
            return
        for bucket in sorted({d[:2] for d in digests}):
            p = self.dir / bucket
            if p.is_dir():
                _fsync_dir(p)
        _fsync_dir(self.dir)

    def digests(self) -> list[str]:
        """All linked object digests (tmp files excluded), sorted."""
        out = []
        if not self.dir.is_dir():
            return out
        for bucket in self.dir.iterdir():
            if not bucket.is_dir():
                continue
            for f in bucket.iterdir():
                if not f.name.startswith(_TMP_PREFIX):
                    out.append(f.name)
        return sorted(out)

    def tmp_files(self) -> list[Path]:
        """Leftover ``.tmp-*`` files from killed publishers (benign; GC'able)."""
        out = []
        if not self.dir.is_dir():
            return out
        for bucket in self.dir.iterdir():
            if bucket.is_dir():
                out.extend(f for f in bucket.iterdir()
                           if f.name.startswith(_TMP_PREFIX))
        return sorted(out)

    # -- fcntl discipline ---------------------------------------------------

    def _lock_fd(self) -> int:
        self.dir.mkdir(parents=True, exist_ok=True)
        return os.open(self.dir / _LOCK_FILE, os.O_CREAT | os.O_RDWR, 0o644)

    def publish_guard(self) -> "_StoreLock":
        """Shared lock: held by a publisher across object writes + commit."""
        return _StoreLock(self._lock_fd(), fcntl.LOCK_SH)

    def sweep_guard(self) -> "_StoreLock":
        """Exclusive lock: held by the GC across mark + sweep."""
        return _StoreLock(self._lock_fd(), fcntl.LOCK_EX)

    def sweep(self, keep: set[str], candidates: dict[str, int] | None = None) -> list[str]:
        """Detach unreferenced objects and hand them to :data:`RECLAIMER`;
        returns the detached digests.

        With ``candidates`` (digest -> bytes, the objects of the manifests
        just dropped) only those not in ``keep`` are detached: no walk of the
        tree, whose listing costs a syscall or more a bucket. Without, every
        linked object not in ``keep`` and every stale tmp file is.

        Caller must hold :meth:`sweep_guard`. Each file is renamed into
        :attr:`reclaim_dir` under a name of its own (digest, pid, counter):
        a digest can be detached, written again by a later publish and
        detached again before the first copy is reclaimed, and a rename
        onto an existing name would unlink that one here, synchronously.
        Counts ``detached_objects`` and ``detached_bytes`` onto the
        caller's open span.
        """
        if candidates is None:
            victims = [(p, None) for p in self.tmp_files()]
            victims += [(self.path(d), None) for d in self.digests() if d not in keep]
        else:
            victims = [(self.path(d), n) for d, n in sorted(candidates.items()) if d not in keep]
        removed: list[str] = []
        detached: list[tuple[Path, int]] = []
        if victims:
            self.reclaim_dir.mkdir(exist_ok=True)
        for src, size in victims:
            dst = self.reclaim_dir / f"{src.name}.{os.getpid()}.{next(_detach_seq)}"
            try:
                if size is None:
                    size = src.stat().st_size
                os.rename(src, dst)
            except FileNotFoundError:  # detached already, by another sweep
                continue
            detached.append((dst, size))
            if not src.name.startswith(_TMP_PREFIX):
                removed.append(src.name)
        annotate(detached_objects=len(detached), detached_bytes=sum(n for _, n in detached))
        if detached or self.reclaim_dir.is_dir():
            RECLAIMER.hand_over(self.reclaim_dir, detached)
        return removed


class Reclaimer:
    """The GC's background work, on one daemon thread started by the first
    call that queues some.

    Each pass over a reclaim directory unlinks everything in it: the files
    handed over and whatever a killed process left there.
    ``cas.gc.mid_sweep`` fires before each unlink. :attr:`backlog_bytes`
    is the bytes handed over and not yet reclaimed. :meth:`later` runs a
    callable on the same thread, before the next passes.
    """

    def __init__(self):
        self._cv = threading.Condition()
        self._queue: dict[Path, list[tuple[Path, int]]] = {}
        self._later: list = []
        self._busy = False
        self._thread: threading.Thread | None = None
        self.backlog_bytes = 0

    def hand_over(self, reclaim_dir: Path, files: list[tuple[Path, int]]) -> None:
        """Queue ``reclaim_dir`` for a pass; ``files`` are (path, bytes) just
        detached into it."""
        with self._cv:
            self._queue.setdefault(reclaim_dir, []).extend(files)
            self.backlog_bytes += sum(n for _, n in files)
            self._wake()

    def later(self, fn) -> None:
        """Run ``fn()`` on the reclaimer thread."""
        with self._cv:
            self._later.append(fn)
            self._wake()

    def _wake(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(target=self._run, name="cas-reclaim", daemon=True)
            self._thread.start()
        self._cv.notify()

    def drain(self, timeout: float | None = None) -> bool:
        """Wait until all queued work is done; False on timeout."""
        with self._cv:
            return self._cv.wait_for(
                lambda: not self._queue and not self._later and not self._busy, timeout)

    def _run(self) -> None:
        while True:
            with self._cv:
                self._cv.wait_for(lambda: self._queue or self._later)
                fns, self._later = self._later, []
                work, self._queue = self._queue, {}
                self._busy = True
            for fn in fns:
                try:
                    fn()
                except Exception:  # the thread serves every store of the process
                    logger.exception("background GC step %r failed", fn)
            for reclaim_dir, files in work.items():
                try:
                    self._pass(reclaim_dir, {p.name: n for p, n in files})
                except Exception:
                    logger.exception("reclaim of %s failed", reclaim_dir)
                finally:
                    with self._cv:
                        self.backlog_bytes -= sum(n for _, n in files)
            with self._cv:
                self._busy = False
                self._cv.notify_all()

    def _pass(self, reclaim_dir: Path, sizes: dict[str, int]) -> None:
        objects = nbytes = 0
        with span("jobstore.reclaim"):
            try:
                entries = list(os.scandir(reclaim_dir))
            except FileNotFoundError:  # the store itself was removed
                entries = []
            for entry in entries:
                size = sizes.get(entry.name)
                try:
                    if size is None:
                        size = entry.stat().st_size
                    faults.fire("cas.gc.mid_sweep")
                    os.unlink(entry.path)
                except FileNotFoundError:  # another process's reclaimer got it
                    continue
                objects += 1
                nbytes += size
            annotate(objects=objects, bytes=nbytes)


RECLAIMER = Reclaimer()


class _StoreLock:
    def __init__(self, fd: int, op: int):
        self.fd = fd
        self.op = op

    def __enter__(self) -> "_StoreLock":
        fcntl.flock(self.fd, self.op)
        return self

    def __exit__(self, *exc) -> None:
        try:
            fcntl.flock(self.fd, fcntl.LOCK_UN)
        finally:
            os.close(self.fd)


def _fsync_dir(path: Path) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


class ObjectWriterPool:
    """Parallel object writer: the CAS analogue of ``_StripedWriterPool``.

    Worker threads drain a bounded queue of ``(digest, buf)`` items into
    :meth:`ObjectStore.put`. Within one save, a digest is submitted at most
    once (the serializer's ``have_digest`` oracle filters dups), but the
    pool still guards with its own seen-set so two identical chunks hashed
    in the same window cannot race. Errors surface at :meth:`close`, which
    also fsyncs every touched bucket directory — objects are fully durable
    when ``close`` returns.
    """

    def __init__(self, store: ObjectStore, threads: int):
        self.store = store
        self.error: Exception | None = None
        self.written_bytes = 0
        self.n_written = 0
        self._digests: set[str] = set()
        self._lock = threading.Lock()
        self.q: queue.Queue = queue.Queue(maxsize=64)
        n = max(1, min(threads, max(2, os.cpu_count() or 1)))
        self.threads = [
            threading.Thread(target=self._run, name=f"cas-writer-{i}", daemon=True)
            for i in range(n)
        ]
        for t in self.threads:
            t.start()

    def _run(self) -> None:
        while True:
            item = self.q.get()
            if item is None:
                break
            if self.error is not None:
                continue  # drain only; the save is already doomed
            digest, buf = item
            try:
                n = self.store.put(digest, buf)
                with self._lock:
                    self.written_bytes += n
                    self.n_written += 1 if n else 0
            except Exception as e:
                self.error = e

    def submit(self, digest: str, buf) -> None:
        if self.error is not None:
            raise self.error
        with self._lock:
            if digest in self._digests:
                return
            self._digests.add(digest)
        self.q.put((digest, buf))

    def close(self) -> tuple[int, int]:
        for _ in self.threads:
            self.q.put(None)
        for t in self.threads:
            t.join()
        if self.error is not None:
            raise self.error
        self.store.fsync_buckets(self._digests)
        return self.written_bytes, self.n_written
