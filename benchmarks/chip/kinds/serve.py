"""Serving traffic: open-loop chat requests into the program's ``ServeHost``.

Two ``ServeHost`` instances share one engine (one chip), each with its own
``DHP`` into one job store: ``s0`` admits every request (prefill, then the
durable publish on admit) and publishes on the program's cadence; a request
chosen to migrate is published on ``s0``, resumed from the store on ``s1``
and dropped on ``s0``, the router's fallback leg. One loop drives both hosts:
it admits every request that is due, migrates those at their step, then
advances each host by one decode step.

Traffic parameters (``traffic/<name>.json``): ``rate_per_s`` (Poisson
arrivals), ``prompt_len`` and ``output_len`` (``{length: share}``),
``migrate_share``, ``schedule_seed``, ``publish_every``, ``chunk_bytes``,
``check_requests``, ``drain_limit_s``. The schedule (when each request is
due, its lengths, whether and where it migrates) is drawn once, from
``schedule_seed``; the run's seed draws the prompts and the weights. So every
seed gets the same work at the same times: with two dozen requests in a
window, an order drawn per seed moved which requests met the window's close
and how many ran at once, and with them every metric.

A request is timed from when it was due. ``correct`` compares a seeded
sample of the finished requests, the longest and a migrated one always
among them, with the plain reference: the widest gap by which a served
token's logit lies below the reference's best at that position.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks.chip import harness

HOSTS = ("s0", "s1")
# What this kind calls of the configuration's reference, beyond what every run does.
REFERENCE = ("served_gaps", "decode_flops", "decode_bytes")


def _counts(shares: dict, n: int) -> dict:
    """Largest-remainder split of ``n`` by ``shares``."""
    raw = {k: n * v for k, v in shares.items()}
    out = {k: int(np.floor(v)) for k, v in raw.items()}
    for k in sorted(raw, key=lambda k: (out[k] - raw[k], k))[: n - sum(out.values())]:
        out[k] += 1
    return out


def schedule(tr: dict, seed: int, seconds: float, vocab: int) -> list[dict]:
    """The requests due in a window of ``seconds``, in due order: the
    traffic's fixed schedule, with prompts drawn from ``seed``."""
    rate = tr["rate_per_s"]
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng(tr["schedule_seed"])
    joint = {(int(p), int(m)): sp * sm for p, sp in tr["prompt_len"].items()
             for m, sm in tr["output_len"].items()}
    shapes = [s for s, k in sorted(_counts(joint, n).items()) for _ in range(k)]
    shapes = [shapes[i] for i in rng.permutation(n)]
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n) / rate
    gaps = gaps[rng.permutation(n)]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    k = int(round(n * tr["migrate_share"]))
    movers = rng.permutation(n)[:k]
    where = ((np.arange(k) + 0.5) / k)[rng.permutation(k)] if k else []
    prompts = np.random.default_rng(seed)
    out = []
    for i, ((p, m), t) in enumerate(zip(shapes, due)):
        out.append({"id": f"q{i:04d}", "due": float(t), "prompt_len": p, "max_new": m,
                    "prompt": prompts.integers(0, vocab, p).astype(np.int32),
                    "migrate_at": None})
    for j, i in enumerate(movers):
        m = out[i]["max_new"]
        out[i]["migrate_at"] = int(min(max(2, round(m * where[j])), m - 1))
    return [r for r in out if r["due"] < seconds]


def build_engine(c: dict, cfg, seed: int):
    """The program's ``ModelEngine`` serving ``cfg`` with the seed's weights.

    The engine builds its model from the program's registry entry, which
    differs from the file's configuration in ``norm_eps`` alone (1e-5, where
    the published config has 1e-6). Its model is rebuilt with the program's
    own ``Model`` class at ``cfg``, and its weights, made from a fixed seed,
    are replaced by the reference's from the run's seed.
    """
    import jax

    from repro.models import Model
    from repro.serve.engine import make_engine

    engine = make_engine(f"model:{c['program']['arch']}:full:seed=0")
    diff = {k: (getattr(engine.cfg, k), getattr(cfg, k)) for k in cfg.__dataclass_fields__
            if getattr(engine.cfg, k) != getattr(cfg, k)}
    if set(diff) - {"norm_eps"}:
        raise RuntimeError(f"the program serves another configuration than the file states: {diff}")
    engine.params = None
    gc.collect()
    engine.cfg, engine.model = cfg, Model(cfg)
    engine._decode_fn = jax.jit(engine.model.decode)
    engine.params = harness.reference(c).make_params(c, seed, c["torch_dtype"])
    _check_layout(engine.params, cfg)
    return engine


def _check_layout(params, cfg) -> None:
    import jax

    from repro.distributed.steps import model_axes_for

    want = model_axes_for(cfg)[1]
    got = [(jax.tree_util.keystr(p), x.shape, str(x.dtype))
           for p, x in jax.tree_util.tree_flatten_with_path(params)[0]]
    exp = [(jax.tree_util.keystr(p), x.shape, str(x.dtype))
           for p, x in jax.tree_util.tree_flatten_with_path(want)[0]]
    if got != exp:
        raise RuntimeError(f"weights do not match the program's layout: "
                           f"{[(g, e) for g, e in zip(got, exp) if g != e][:4]}")


class Loop:
    """The serving loop: due requests, migrations, one decode step per host."""

    def __init__(self, h, hosts, jobs, requests):
        self.h, self.hosts, self.jobs = h, hosts, jobs
        self.requests = requests
        self.req = {r["id"]: dict(r, tokens=[], times=[], host=None, job=None,
                                  migrated_t=None, finished_t=None, prefill_s=None,
                                  resumed_ok=None)
                    for r in requests}
        self.decode_calls: list[tuple[float, float, int, int]] = []  # (t_begin, t_end, pos, s_max)

    def admit(self, r) -> None:
        spans = self.h.spans
        st = self.req[r["id"]]
        st["job"] = self.jobs.create_job({"req": r["id"]}).job_id
        spans.begin("bench.admit")
        res = self.hosts["s0"].admit(r["id"], r["prompt"], r["max_new"], job_id=st["job"])
        t = spans.end("bench.admit")
        st.update(host="s0", prefill_s=res["prefill_s"], admitted_t=t)
        for i, tok in res["tokens"]:
            st["tokens"].append((i, tok))
            st["times"].append(t)

    def migrate(self, rid: str) -> None:
        spans = self.h.spans
        st = self.req[rid]
        src, dst = self.hosts["s0"], self.hosts["s1"]
        st["migrated_t"] = time.perf_counter()
        spans.begin("bench.migrate")
        src.publish(rid)
        res = dst.resume(rid, st["job"])
        src.drop(rid)
        spans.end("bench.migrate")
        st["host"] = "s1"
        st["resumed_ok"] = [tuple(x) for x in res["tokens"]] == st["tokens"]

    def step(self, name: str) -> None:
        host, spans = self.hosts[name], self.h.spans
        calls = [(int(s["pos"]), int(s["out"].shape[0] + s["prompt"].shape[0]))
                 for s in host.active.values() if int(s["done"]) < int(s["out"].shape[0])]
        t0 = time.perf_counter()
        spans.begin("bench.decode_step")
        out = host.step()
        t = spans.end("bench.decode_step")
        self.decode_calls.extend((t0, t, pos, s_max) for pos, s_max in calls)
        for rid, toks in out["tokens"].items():
            st = self.req[rid]
            for i, tok in toks:
                st["tokens"].append((i, tok))
                st["times"].append(t)
        for rid in out["finished"]:
            self.req[rid]["finished_t"] = t

    def run(self, t0: float, seconds: float, drain_limit_s: float) -> float:
        """Drive until every request is finished; returns the window's close.

        The drain's limit runs from when the trace, if any, has been written,
        so that a late answer is waited for and not cut off.
        """
        pending = list(self.requests)
        t1 = drained = None
        while True:
            now = time.perf_counter()
            if t1 is None and now - t0 >= seconds:
                t1 = now
                self.h.spans.end("bench.window")
                self.h.on_window_closed()
                drained = now = time.perf_counter()
            if t1 is not None and now - drained > drain_limit_s:
                break
            while pending and pending[0]["due"] <= now - t0:
                self.admit(pending.pop(0))
            for rid in sorted(self.hosts["s0"].active):
                st = self.req[rid]
                state = self.hosts["s0"].active[rid]
                if st["migrate_at"] is not None and st["migrated_t"] is None \
                        and int(state["done"]) >= st["migrate_at"]:
                    self.migrate(rid)
            busy = False
            for name in HOSTS:
                if self.hosts[name].active:
                    self.step(name)
                    busy = True
            if not busy:
                if not pending:
                    if t1 is not None:
                        break
                    time.sleep(min(0.01, max(0.0, t0 + seconds - time.perf_counter())))
                    continue
                wait = t0 + pending[0]["due"] - time.perf_counter()
                if t1 is None:
                    wait = min(wait, t0 + seconds - time.perf_counter())
                if wait > 0:
                    self.h.spans.begin("bench.idle")
                    time.sleep(wait)
                    self.h.spans.end("bench.idle")
        return t1 if t1 is not None else time.perf_counter()


def run(h) -> dict:
    from repro.core import DHP, NBS, JobStore
    from repro.serve.worker import ServeHost

    c, tr = h.config, h.traffic
    cfg = harness.program_config(c)
    seed = harness.derived_seed(h.seed, "serve")
    engine = build_engine(c, cfg, seed)

    jobs = JobStore(h.work / "jobs")
    nbs = NBS(str(h.work / "nbs"))
    hosts = {}
    for name in HOSTS:
        nbs.add_node(name, mesh=None)
        hosts[name] = ServeHost(engine, node_name=name,
                                dhp=DHP(nbs, name, jobs, chunk_bytes=tr["chunk_bytes"]),
                                publish_every=tr["publish_every"])

    # warm every shape the traffic uses: prefill, the admit publish, decode
    # on s0, a migration, decode of the resumed state on s1
    vocab = c["vocab_size"]
    for p in sorted(int(x) for x in tr["prompt_len"]):
        for m in sorted(int(x) for x in tr["output_len"]):
            rid = f"warm-{p}-{m}"
            job = jobs.create_job({"req": rid}).job_id
            hosts["s0"].admit(rid, np.arange(p, dtype=np.int32) % vocab, m, job_id=job)
            hosts["s0"].step()
            hosts["s0"].publish(rid)
            hosts["s1"].resume(rid, job)
            hosts["s0"].drop(rid)
            hosts["s1"].step()
            hosts["s1"].drop(rid)

    requests = schedule(tr, seed, h.seconds, vocab)
    loop = Loop(h, hosts, jobs, requests)
    h.start_window()
    t0 = time.perf_counter()
    h.spans.begin("bench.window")
    t1 = loop.run(t0, h.seconds, tr["drain_limit_s"])
    memory = harness.memory_peak_bytes()
    del hosts, loop.hosts
    engine.params = None
    del engine
    gc.collect()
    unfinished = sum(1 for r in loop.req.values() if r["finished_t"] is None)
    return {"kind": "serve", "loop": loop, "memory_peak_bytes": memory,
            "setup_end": t0, "window": (t0, t1), "attempted": len(requests),
            "failed": unfinished, "seed31": seed, "cfg": cfg}


def sample(out: dict, n: int) -> list[dict]:
    """The finished requests the check compares: the longest, a migrated
    one drawn from the seed (where one finished), and a seeded draw."""
    done = sorted((r for r in out["loop"].req.values() if r["finished_t"] is not None),
                  key=lambda r: r["id"])
    if not done:
        return []
    rng = np.random.default_rng(out["seed31"] + 1)
    longest = max(done, key=lambda r: (r["prompt_len"] + r["max_new"], r["id"]))
    pick = [longest]
    moved = [r for r in done if r["migrated_t"] is not None and r is not longest]
    if moved and longest["migrated_t"] is None:
        pick.append(moved[rng.integers(len(moved))])
    rest = [r for r in done if all(r is not p for p in pick)]
    return pick + [rest[i] for i in rng.permutation(len(rest))[: max(0, n - len(pick))]]


def check(h, out: dict, fmt=None) -> dict:
    """Widest served-token gap over the sample, and what else must hold.

    With ``fmt`` the reference in that operand format stands in for the
    program (the control): ``served_gap`` is then the widest gap of the
    tokens it would have put first at the same positions.
    """
    c, tr = h.config, h.traffic
    ref = harness.reference(c)
    reqs = list(out["loop"].req.values())
    unfinished = sum(1 for r in reqs if r["finished_t"] is None)
    torn = sum(1 for r in reqs if r["resumed_ok"] is False)
    params = ref.make_params(c, out["seed31"], c["torch_dtype"])
    gap = 0.0
    for r in sample(out, tr["check_requests"]):
        served = [tok for _, tok in sorted(r["tokens"])]
        if len(served) != r["max_new"] or [i for i, _ in sorted(r["tokens"])] != list(range(r["max_new"])):
            torn += 1
            continue
        g = ref.served_gaps(params, r["prompt"], served, c, fmt)
        gap = max(gap, g["served_gap"] if fmt is None else g["control_gap"])
    del params
    return {"served_gap": gap, "unfinished": unfinished, "torn": torn}
