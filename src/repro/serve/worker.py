"""Serving worker: a fabric node whose resident states are in-flight requests.

``python -m repro.serve.worker --name s0 --socket /tmp/s0.sock --store S
--jobstore J --serve-only --engine toy``

:class:`ServeHost` is the continuous-batching loop behind the ``svc/serve_*``
services. The "batch" is a rolling *set*: a request joins at admit (prefill),
every ``svc/serve_step`` advances each active request by exactly one decode
step, and a request leaves alone at EOS — there is no batch barrier, so
requests at wildly different positions coexist and churn never stalls the
others.

Each request is a jobstore job; its engine state (KV cache + position, see
``repro.serve.engine``) is the CMI. The host publishes it content-addressed
(CAS v4) right after prefill — from that moment the prefill work is durable
and a no-notice SIGKILL costs at most ``publish_every`` decode steps — and
again on cadence and on SIGTERM notice.

Live migration is two phases over the streamed-hop wire (pre-copy, the VM
live-migration shape):

    warm     stream the full request state to the destination; it stays
             resident there (NOT active) and both sides keep the chunk-hash
             grid. Decode continues HERE — the warm copy goes stale by
             exactly the rows decoded after it.
    handoff  delta-stream against the warm baseline (only the rows written
             since the warm copy travel), then tell the destination to adopt
             the fresh token into its active set and drop the warm copy.
             The destination resumes decode at ``pos`` — zero re-prefill.

Either phase failing is safe: a torn warm copy just means the handoff
streams full; a torn handoff leaves the request active here (baselines
invalidated) and the router falls back to publish + resume via the store.

Services (all plain wire data, registered on the NBS node so NodeServer's
dispatch fallthrough serves them):

    svc/serve_admit    prefill + first publish; returns the first token
    svc/serve_step     one decode step for every active request
    svc/serve_status   per-request positions + lifetime counters
    svc/serve_publish  force a CMI publish for one request
    svc/serve_warm     pre-copy phase 1 (full/refresh stream to dest)
    svc/serve_handoff  pre-copy phase 2 (delta stream + remote adopt)
    svc/serve_adopt    destination side: resident token -> active request
    svc/serve_resume   restore a request from its last published CMI
    svc/serve_drop     forget a request (after a confirmed handoff)
    svc/serve_drain    hand every active request to one destination
"""

from __future__ import annotations

import os
import signal
import sys
import threading
from typing import Any

import numpy as np

from repro.chaos import faults
from repro.core.jobstore import STATUS_CKPT, STATUS_FINISHED
from repro.serve.engine import is_done, make_engine, transcript
from repro.spans import span
from repro.utils import enable_compile_cache, logger

EXIT_FINISHED = 0
EXIT_PREEMPTED = 43


class ServeHost:
    """Continuous-batching state machine for one serving worker.

    Runs identically in-process (``launch/serve.py --workers 0``, the bench
    reference) and behind a :class:`~repro.fabric.server.NodeServer` — the
    fabric pieces (``dhp``, ``server``) are optional and only gate publish /
    migration, never decode semantics.
    """

    def __init__(
        self,
        engine,
        *,
        node_name: str = "serve",
        dhp=None,
        server=None,
        publish_every: int = 0,
        chunk_bytes: int = 1 << 20,
    ):
        self.engine = engine
        self.node_name = node_name
        self.dhp = dhp
        self.server = server  # NodeServer: resident/stream_grids for adopt
        self.publish_every = int(publish_every)
        self.chunk_bytes = int(chunk_bytes)
        self.active: dict[str, dict] = {}  # req_id -> engine state
        self.jobs: dict[str, str] = {}  # req_id -> job_id
        self.counters = {
            "prefills": 0, "decode_steps": 0, "publishes": 0,
            "migrations_in": 0, "migrations_out": 0, "resumes": 0,
        }
        # (req_id, dest address) -> (resident token on dest, sent grid,
        # done at warm time): the delta baseline for that request's handoff.
        # Per-REQUEST, not per-destination — concurrent migrations of
        # different requests to one worker must not clobber each other
        # (the fabric's relay keeps per-dest baselines; serve cannot).
        self._warm: dict[tuple[str, tuple], tuple[str, dict, int]] = {}
        self._since_publish: dict[str, int] = {}
        self._lock = threading.RLock()

    # -- service registration ------------------------------------------------
    def register(self, node) -> None:
        """Expose the serve services on an NBS node (plain-data handlers, so
        NodeServer's dispatch fallthrough serves them over the wire)."""
        node.register("svc/serve_admit", self.admit)
        node.register("svc/serve_step", self.step)
        node.register("svc/serve_status", self.status)
        node.register("svc/serve_publish", self.publish)
        node.register("svc/serve_warm", self.warm)
        node.register("svc/serve_handoff", self.handoff)
        node.register("svc/serve_adopt", self.adopt)
        node.register("svc/serve_resume", self.resume)
        node.register("svc/serve_drop", self.drop)
        node.register("svc/serve_drain", self.drain)

    # -- admit / step / status -----------------------------------------------
    def admit(self, req_id: str, prompt: list, max_new: int,
              job_id: str | None = None) -> dict:
        with self._lock:
            faults.fire("serve.admit")
            if req_id in self.active:
                raise ValueError(f"request {req_id!r} already active")
            with span("engine.prefill", job=job_id) as sp:
                state = self.engine.prefill(np.asarray(prompt, np.int32), int(max_new))
            self.counters["prefills"] += 1
            self.active[req_id] = state
            if job_id is not None:
                self.jobs[req_id] = job_id
            self._since_publish[req_id] = 0
            # durable immediately: prefill is the "hours of work" — from here
            # on even a no-notice kill resumes with zero re-prefill
            self._publish_ckpt(req_id)
            return {
                "id": req_id,
                "tokens": [[0, int(state["out"][0])]],
                "pos": int(state["pos"]),
                "done": int(state["done"]),
                "prefill_s": sp.duration,
                "prompt_tokens": int(np.asarray(prompt).size),
            }

    def step(self) -> dict:
        """One decode step for EVERY active request (rolling batch: each
        request advances independently; finished ones leave alone)."""
        with self._lock:
            tokens: dict[str, list[list[int]]] = {}
            finished: list[str] = []
            for req_id in sorted(self.active):
                state = self.active[req_id]
                if is_done(state):
                    finished.append(req_id)
                    continue
                with span("engine.decode", job=self.jobs.get(req_id)):
                    state = self.engine.decode(state)
                self.active[req_id] = state
                self.counters["decode_steps"] += 1
                tokens[req_id] = [[int(state["done"]) - 1, int(state["tok"])]]
                if is_done(state):
                    finished.append(req_id)
                else:
                    self._since_publish[req_id] = self._since_publish.get(req_id, 0) + 1
                    if self.publish_every > 0 and \
                            self._since_publish[req_id] >= self.publish_every:
                        self._publish_ckpt(req_id)
            for req_id in finished:
                self._finish(req_id)
            return {"tokens": tokens, "finished": finished, "active": len(self.active)}

    def status(self) -> dict:
        with self._lock:
            return {
                "node": self.node_name,
                "engine": self.engine.spec(),
                "platform": self.engine.platform,
                "counters": dict(self.counters),
                "requests": {
                    req_id: {"pos": int(st["pos"]), "done": int(st["done"]),
                             "eos": is_done(st)}
                    for req_id, st in self.active.items()
                },
            }

    def _finish(self, req_id: str) -> None:
        state = self.active.pop(req_id, None)
        self._since_publish.pop(req_id, None)
        job_id = self.jobs.pop(req_id, None)
        if state is None:
            return
        if self.dhp is not None and job_id is not None:
            self.dhp.publish(
                job_id, STATUS_FINISHED,
                product={"tokens": np.asarray(state["out"]), "req_id": req_id},
                step=int(state["done"]),
            )

    # -- publish / resume (the store leg) ------------------------------------
    def _publish_ckpt(self, req_id: str) -> str | None:
        if self.dhp is None:
            return None
        job_id = self.jobs.get(req_id)
        if job_id is None:
            return None
        state = self.active[req_id]
        name = self.dhp.publish(job_id, STATUS_CKPT, state, step=int(state["done"]))
        self.counters["publishes"] += 1
        self._since_publish[req_id] = 0
        return name

    def publish(self, req_id: str) -> dict:
        with self._lock:
            if req_id not in self.active:
                raise KeyError(f"no active request {req_id!r}")
            name = self._publish_ckpt(req_id)
            if name is None:
                raise RuntimeError("this host has no jobstore to publish into")
            return {"cmi": name, "step": int(self.active[req_id]["done"])}

    def publish_all(self) -> int:
        """SIGTERM-notice path: make every in-flight request durable."""
        with self._lock:
            n = 0
            for req_id in sorted(self.active):
                if self._publish_ckpt(req_id) is not None:
                    n += 1
            if self.dhp is not None:
                self.dhp.flush()
            return n

    def resume(self, req_id: str, job_id: str) -> dict:
        """Restore a request from its last published CMI and join the batch.

        Zero re-prefill by construction: the CMI holds the cache rows the
        original prefill (and every decode step up to the publish) wrote.
        """
        with self._lock:
            if self.dhp is None:
                raise RuntimeError("this host has no jobstore to resume from")
            if req_id in self.active:
                raise ValueError(f"request {req_id!r} already active")
            state, _ = self.dhp.restart(job_id)
            state = {**state, "out": np.asarray(state["out"], np.int32),
                     "prompt": np.asarray(state["prompt"], np.int32),
                     "pos": int(state["pos"]), "done": int(state["done"]),
                     "tok": int(state["tok"])}
            self.active[req_id] = state
            self.jobs[req_id] = job_id
            self._since_publish[req_id] = 0
            self.counters["resumes"] += 1
            return {
                "id": req_id,
                "pos": int(state["pos"]),
                "done": int(state["done"]),
                "tokens": [[i, t] for i, t in enumerate(transcript(state))],
            }

    def drop(self, req_id: str) -> dict:
        with self._lock:
            gone = self.active.pop(req_id, None) is not None
            self.jobs.pop(req_id, None)
            self._since_publish.pop(req_id, None)
            return {"dropped": gone}

    # -- live migration (the stream leg) -------------------------------------
    def _stream_to(self, req_id: str, dest: tuple, baseline) -> tuple[dict, dict]:
        from repro.fabric import stream

        state = self.active[req_id]
        baseline_token, baseline_grid = (baseline[0], baseline[1]) if baseline else (None, None)
        return stream.send_state_stream(
            tuple(dest), state,
            src=self.node_name, step=int(state["done"]),
            chunk_bytes=self.chunk_bytes,
            baseline_token=baseline_token, baseline_grid=baseline_grid,
            fault_point="serve.migrate.mid_stream",
        )

    def warm(self, req_id: str, dest) -> dict:
        """Pre-copy phase 1: park a copy of the request on ``dest``.

        Decode continues here — the copy goes stale by exactly the rows
        decoded after this call, which is precisely what the handoff's
        delta stream will ship. A repeat warm to the same dest is itself a
        delta against the previous warm copy.
        """
        with self._lock:
            if req_id not in self.active:
                raise KeyError(f"no active request {req_id!r}")
            dest_addr = tuple(dest)
            key = (req_id, dest_addr)
            try:
                receipt, grid = self._stream_to(req_id, dest_addr, self._warm.get(key))
            except Exception:
                self._warm.pop(key, None)  # dest state unknowable: never delta
                raise
            stale = self._warm.get(key)
            self._warm[key] = (receipt["token"], grid, int(self.active[req_id]["done"]))
            if stale is not None:
                self._drop_remote(dest_addr, stale[0])
            return {"token": receipt["token"], "chunks": receipt["chunks"],
                    "data_chunks": receipt["data_chunks"],
                    "ref_chunks": receipt["ref_chunks"],
                    "done": int(self.active[req_id]["done"])}

    def handoff(self, req_id: str, dest) -> dict:
        """Pre-copy phase 2: delta-stream against the warm copy, then the
        destination adopts the request and decode continues THERE.

        Works without a prior warm too — the stream is simply full. On any
        failure the request stays active here and the caller falls back to
        publish + resume.
        """
        with self._lock:
            if req_id not in self.active:
                raise KeyError(f"no active request {req_id!r}")
            dest_addr = tuple(dest)
            key = (req_id, dest_addr)
            warm = self._warm.get(key)
            try:
                receipt, _grid = self._stream_to(req_id, dest_addr, warm)
            except Exception:
                self._warm.pop(key, None)
                raise
            adopted = self._adopt_remote(
                dest_addr, req_id, receipt["token"], self.jobs.get(req_id),
                drop_token=warm[0] if warm else None,
            )
            self._warm.pop(key, None)
            self.active.pop(req_id, None)
            self.jobs.pop(req_id, None)
            self._since_publish.pop(req_id, None)
            self.counters["migrations_out"] += 1
            return {
                "id": req_id,
                "node": adopted.get("node"),
                "pos": adopted["pos"],
                "done": adopted["done"],
                "chunks": receipt["chunks"],
                "data_chunks": receipt["data_chunks"],
                "ref_chunks": receipt["ref_chunks"],
                "sent_bytes": receipt["sent_bytes"],
                "warm": warm is not None,
            }

    def adopt(self, req_id: str, token: str, job_id: str | None = None,
              drop_token: str | None = None) -> dict:
        """Destination side of a handoff: promote the streamed-in resident
        state to an active request. No prefill happens — ``pos`` carries on
        exactly where the source stopped."""
        with self._lock:
            if self.server is None:
                raise RuntimeError("adopt needs a NodeServer (resident states)")
            if req_id in self.active:
                raise ValueError(f"request {req_id!r} already active")
            entry = self.server.resident.pop(token, None)
            self.server.stream_grids.pop(token, None)
            if entry is None:
                raise KeyError(f"no resident state {token!r}")
            if drop_token is not None:  # retire the warm copy
                self.server.resident.pop(drop_token, None)
                self.server.stream_grids.pop(drop_token, None)
            state = entry[0]
            state = {**state, "out": np.asarray(state["out"], np.int32),
                     "prompt": np.asarray(state["prompt"], np.int32),
                     "pos": int(state["pos"]), "done": int(state["done"]),
                     "tok": int(state["tok"])}
            self.active[req_id] = state
            if job_id is not None:
                self.jobs[req_id] = job_id
            self._since_publish[req_id] = 0
            self.counters["migrations_in"] += 1
            return {"id": req_id, "node": self.node_name,
                    "pos": int(state["pos"]), "done": int(state["done"])}

    def drain(self, dest) -> dict:
        """Hand every active request to ``dest`` (the upgrade path).

        All-or-nothing is NOT required: each request hands off
        independently, and any failure surfaces so the router can finish
        the drain per-request with its own fallbacks.
        """
        with self._lock:
            faults.fire("serve.drain")
            moved = []
            for req_id in sorted(self.active):
                self.handoff(req_id, dest)
                moved.append(req_id)
            return {"moved": moved}

    # -- remote control calls (short-lived client per call) ------------------
    def _adopt_remote(self, dest_addr: tuple, req_id: str, token: str,
                      job_id: str | None, drop_token: str | None) -> dict:
        from repro.fabric.proxy import FabricClient

        with FabricClient(dest_addr) as client:
            return client.request(
                "svc/serve_adopt", req_id=req_id, token=token,
                job_id=job_id, drop_token=drop_token,
            )

    def _drop_remote(self, dest_addr: tuple, token: str) -> None:
        from repro.fabric.proxy import FabricClient

        try:
            with FabricClient(dest_addr) as client:
                client.request("svc/drop", token=token)
        except Exception:  # best-effort: a stale warm copy is only memory
            logger.warning("could not retire stale warm copy %s on %s",
                           token, dest_addr)


# ---------------------------------------------------------------------------
# entrypoint
# ---------------------------------------------------------------------------


def build_parser():
    from repro.fabric import worker as fabric_worker

    ap = fabric_worker.build_parser()
    ap.prog = "repro.serve.worker"
    ap.add_argument("--engine", default="toy",
                    help="engine spec: toy[:d=..,vocab=..,seed=..] or "
                         "model:<arch>[:smoke|full][:seed=N]")
    ap.add_argument("--serve-chunk-bytes", type=int, default=1 << 20,
                    help="stream/publish chunk size for request state")
    return ap


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    if args.tcp:
        host, _, port = args.tcp.rpartition(":")
        address = ("tcp", host or "127.0.0.1", int(port or 0))
    elif args.socket:
        address = ("unix", args.socket)
    else:
        raise SystemExit("serve worker needs --socket or --tcp")

    faults.set_role("worker", node=args.name)
    enable_compile_cache()
    engine = make_engine(args.engine)

    from repro.core.dhp import DHP
    from repro.core.jobstore import JobStore
    from repro.core.nbs import NBS
    from repro.core.preemption import PreemptionNotice
    from repro.fabric.server import NodeServer

    nbs = NBS(args.store)
    node = nbs.add_node(args.name, mesh=None)
    jobstore = JobStore(args.jobstore) if args.jobstore else None
    server = NodeServer(nbs, args.name, address, jobstore=jobstore).start()
    dhp = DHP(nbs, args.name, jobstore, chunk_bytes=args.serve_chunk_bytes) \
        if jobstore is not None else None
    host = ServeHost(
        engine, node_name=args.name, dhp=dhp, server=server,
        publish_every=args.publish_every, chunk_bytes=args.serve_chunk_bytes,
    )
    host.register(node)

    notice = PreemptionNotice()
    if os.environ.get("REPRO_CHAOS_IGNORE_SIGTERM"):
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
    else:
        notice.install_sigterm(args.grace_s)

    if args.ready_file:
        import json
        from pathlib import Path

        tmp = Path(args.ready_file + ".tmp")
        tmp.write_text(json.dumps({"pid": os.getpid(), "address": list(server.address)}))
        os.replace(tmp, args.ready_file)

    heartbeat_stop: threading.Event | None = None
    if args.registry:
        from repro.fabric.registry import RegistryClient, tcp_address

        registry = RegistryClient(tcp_address(args.registry))
        generation = registry.register(
            args.name, server.address, pid=os.getpid(), kind="worker"
        )
        heartbeat_stop = registry.start_heartbeat(
            args.name, generation, interval_s=args.heartbeat_s
        )

    try:
        server.serve_forever(until=notice.imminent)
        if notice.imminent():
            # the 2-minute notice: this is the migrate-or-publish moment.
            # The router may already have drained us; whatever is still
            # active goes durable so the resume leg loses at most the steps
            # since the last publish (a sigkill at this very point degrades
            # to exactly that).
            try:
                faults.fire("serve.reclaim.notice")
                n = host.publish_all()
                logger.warning("serve worker %s preempted; published %d in-flight "
                               "requests before exit", args.name, n)
            except Exception:
                logger.exception("notice-path publish failed; last cadence "
                                 "publishes remain authoritative")
            return EXIT_PREEMPTED
        return EXIT_FINISHED
    finally:
        if heartbeat_stop is not None:
            heartbeat_stop.set()
        server.stop()


if __name__ == "__main__":
    sys.exit(main())
