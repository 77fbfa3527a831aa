"""Elastic serving: continuous batching, live migration, kill-resume.

The subsystem's one invariant — a request's transcript is a pure function
of (engine seed, prompt, max_new) — is asserted here across every way a
request can travel: staggered admits into a rolling batch, a pre-copy
migration over the streamed delta hop (the on-the-wire chunk count is
pinned: only rows decoded since the warm baseline ship), the store
fallback when the stream path is armed to die, a SIGTERM-notice publish,
and a no-notice SIGKILL with resume from the last published CMI.

Process-spawning tests use the same SIGALRM guard as tests/test_fabric.py.
"""

import os
import signal

import numpy as np
import pytest

from repro import spans
from repro.core import DHP, NBS
from repro.core.cmi import restore_cmi
from repro.core.jobstore import JobStore, STATUS_FINISHED
from repro.fabric.server import NodeServer
from repro.serve.engine import ToyEngine, make_engine, run_reference
from repro.serve.router import ServeRouter
from repro.serve.worker import ServeHost

PER_TEST_TIMEOUT_S = int(os.environ.get("NAVP_TEST_TIMEOUT", "180"))

SPEC = "toy:d=64,vocab=256,seed=3"
REQS = [
    {"id": f"q{i}", "prompt": [5 + 3 * i, 40, 17 + i, 8], "max_new": 12}
    for i in range(4)
]


@pytest.fixture(autouse=True)
def _alarm_guard():
    def on_alarm(signum, frame):
        raise TimeoutError(f"serve test exceeded {PER_TEST_TIMEOUT_S}s")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(PER_TEST_TIMEOUT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


# ---------------------------------------------------------------------------
# engine contract
# ---------------------------------------------------------------------------


def test_engine_determinism_and_spec_roundtrip():
    a = run_reference(make_engine(SPEC), REQS)
    b = run_reference(make_engine(make_engine(SPEC).spec()), REQS)
    assert a == b
    # transcripts must not be degenerate (a constant stream would let a torn
    # migration pass silently)
    assert all(len(set(t)) > 1 for t in a.values())
    assert len({tuple(t) for t in a.values()}) == len(REQS)


def test_engine_append_only_cache_growth():
    eng = ToyEngine(d=16, vocab=64, seed=0)
    state = eng.prefill([1, 2, 3], 8)
    pos0 = int(state["pos"])
    before = state["kv"][:pos0].copy()
    for _ in range(5):
        eng.decode(state)
    # decode wrote ONLY rows pos0.. — everything earlier is byte-identical
    assert state["kv"][:pos0].tobytes() == before.tobytes()
    assert int(state["pos"]) == pos0 + 5
    assert int(state["done"]) == 6  # prefill's first token + 5 decodes


def test_engine_rejects_empty_prompt():
    with pytest.raises(ValueError):
        ToyEngine().prefill([], 4)


def test_model_engine_deterministic_rebuild():
    # params re-derived from the seed in a fresh engine: same transcript
    reqs = [{"id": "m0", "prompt": [3, 1, 4, 1, 5], "max_new": 4}]
    a = run_reference(make_engine("model:qwen3-1.7b:smoke:seed=0"), reqs)
    b = run_reference(make_engine("model:qwen3-1.7b:smoke:seed=0"), reqs)
    assert a == b
    assert len(a["m0"]) == 4


def _prefill_compiled(eng, prompt, max_new):
    """``eng.prefill`` inside an ``engine.prefill`` span, as ``ServeHost.admit``
    calls it: (state, the span's ``compiled`` counter)."""
    with spans.span("engine.prefill"):
        state = eng.prefill(prompt, max_new)
    return state, spans.last().attrs["compiled"]


def test_model_engine_compiles_prefill_once_a_shape():
    eng = make_engine("model:qwen3-1.7b:smoke:seed=0")
    seen = [_prefill_compiled(eng, prompt, m)[1] for prompt, m in [
        ([3, 1, 4, 1, 5], 4), ([2, 7, 1, 8, 2], 4),  # one shape: built, then reused
        ([3, 1, 4, 1, 5, 9], 4),  # another prompt length
        ([3, 1, 4, 1, 5], 6),  # another answer length: another cache length
        ([9, 9, 9, 9, 9], 6)]]
    assert seen == [1, 0, 1, 1, 0]


def _float32_engine(arch):
    """A smoke engine of ``arch`` rebuilt in float32, as the benchmark
    harness rebuilds an engine's model and weights."""
    import jax

    from repro.models import Model

    eng = make_engine(f"model:{arch}:smoke:seed=0")
    eng.cfg = eng.cfg.with_(dtype="float32")
    eng.model = model = Model(eng.cfg)
    eng.params = jax.jit(lambda key: model.init(key)[0])(jax.random.PRNGKey(1))
    return eng


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "moonlight-16b-a3b"])
def test_compiled_prefill_equals_the_eager_model_prefill(arch):
    import jax
    import jax.numpy as jnp

    eng = _float32_engine(arch)
    prompt = np.arange(3, 40, dtype=np.int32) % eng.vocab
    state, compiled = _prefill_compiled(eng, prompt, 5)
    counts = {k: v for k, v in spans.last().attrs.items() if k not in ("compiled", "tokens")}
    logits, caches, want = eng.model.prefill(
        eng.params, {"tokens": jnp.asarray(prompt[None, :])}, s_max=42, counts=True)
    assert compiled == 1
    assert state["tok"] == int(jnp.argmax(logits[0])) and state["pos"] == 37
    assert counts == {k: int(v) for k, v in want.items()}
    assert bool(counts) == (arch == "moonlight-16b-a3b")
    jax.tree_util.tree_map(np.testing.assert_array_equal, state["caches"], caches)


def test_run_reference_transcripts_match_the_eager_prefill():
    """The compiled prefill leaves every transcript as the eager
    ``Model.prefill`` followed by the engine's decode makes it."""
    import jax.numpy as jnp

    eng = make_engine("model:qwen3-1.7b:smoke:seed=0")
    reqs = [{"id": f"m{i}", "prompt": [3 + i, 1, 4, 1, 5][: 3 + i], "max_new": 4 + i}
            for i in range(3)]
    want = {}
    for req in reqs:
        prompt = np.asarray(req["prompt"], np.int32)
        logits, caches = eng.model.prefill(eng.params, {"tokens": jnp.asarray(prompt[None, :])},
                                           s_max=prompt.size + req["max_new"])
        tok = int(jnp.argmax(logits[0]))
        out = np.zeros(req["max_new"], np.int32)
        out[0] = tok
        state = {"caches": caches, "out": out, "prompt": prompt,
                 "pos": prompt.size, "done": 1, "tok": tok, "step": 1}
        while int(state["done"]) < req["max_new"]:
            state = eng.decode(state)
        want[req["id"]] = [int(t) for t in state["out"]]
    assert run_reference(eng, reqs) == want


def test_replaced_model_gets_programs_of_its_own():
    """A model put in place of the engine's, as the benchmark harness puts
    one, is traced anew; the program of the model it replaced never runs."""
    import jax
    import jax.numpy as jnp

    from repro.models import Model

    class Traced(Model):
        def __init__(self, cfg):
            super().__init__(cfg)
            self.traced = 0

        def prefill(self, *args, **kw):
            self.traced += 1
            return super().prefill(*args, **kw)

    eng = make_engine("model:qwen3-1.7b:smoke:seed=0")
    eng.model = old = Traced(eng.cfg)
    prompt = np.asarray([3, 1, 4, 1, 5], np.int32)
    before, compiled = _prefill_compiled(eng, prompt, 4)
    assert compiled == 1
    # another rotary base: the same tree of weights, other caches
    eng.cfg = eng.cfg.with_(rope_theta=eng.cfg.rope_theta / 7)
    eng.model = new = Traced(eng.cfg)
    state, compiled = _prefill_compiled(eng, prompt, 4)
    assert compiled == 1 and (old.traced, new.traced) == (1, 1)
    _, want = Model(eng.cfg).prefill(eng.params, {"tokens": jnp.asarray(prompt[None, :])}, s_max=9)
    jax.tree_util.tree_map(np.testing.assert_array_equal, state["caches"], want)
    assert not np.array_equal(before["caches"]["g0"]["k"], state["caches"]["g0"]["k"])
    assert _prefill_compiled(eng, prompt, 4)[1] == 0 and new.traced == 1


# ---------------------------------------------------------------------------
# in-process continuous batching
# ---------------------------------------------------------------------------


def test_rolling_batch_staggered_admits():
    """Requests join mid-flight and leave alone at EOS; the rolling set
    never stalls anyone, and transcripts match the sequential oracle."""
    expected = run_reference(make_engine(SPEC), REQS)
    host = ServeHost(make_engine(SPEC))
    got = {}
    for req in REQS:  # each admit lands while earlier requests are decoding
        res = host.admit(req["id"], req["prompt"], req["max_new"])
        got[req["id"]] = [tok for _, tok in res["tokens"]]
        for rid, toks in host.step()["tokens"].items():
            got[rid].extend(tok for _, tok in toks)
    while host.active:
        for rid, toks in host.step()["tokens"].items():
            got[rid].extend(tok for _, tok in toks)
    assert got == expected
    assert host.counters["prefills"] == len(REQS)
    assert host.counters["migrations_in"] == 0


def test_admit_twice_rejected():
    host = ServeHost(make_engine(SPEC))
    host.admit("dup", [1, 2], 4)
    with pytest.raises(ValueError):
        host.admit("dup", [1, 2], 4)


# ---------------------------------------------------------------------------
# in-process fleet (real NodeServers + wire, no spawned processes)
# ---------------------------------------------------------------------------


def _mk_fleet(tmp_path, names=("s0", "s1"), *, chunk_bytes=4096,
              publish_every=3):
    nbs = NBS(tmp_path / "store")
    js = JobStore(tmp_path / "jobs")
    hosts, servers = {}, {}
    for name in names:
        node = nbs.add_node(name, mesh=None)
        srv = NodeServer(nbs, name, ("unix", str(tmp_path / f"{name}.sock")),
                         jobstore=js).start()
        host = ServeHost(make_engine(SPEC), node_name=name,
                         dhp=DHP(nbs, name, js, chunk_bytes=chunk_bytes),
                         server=srv, publish_every=publish_every,
                         chunk_bytes=chunk_bytes)
        host.register(node)
        hosts[name], servers[name] = host, srv
    router = ServeRouter(jobstore=js)
    for name, srv in servers.items():
        router.add_worker(name, srv.address)
    return js, hosts, servers, router


def _teardown(servers, router):
    router.close()
    for srv in servers.values():
        srv.stop()


def test_migration_ships_only_rows_since_warm(tmp_path):
    """The append-only KV delta property, on the wire.

    d=64 float64 rows are 512 B; chunk_bytes=4096 packs 8 rows per chunk.
    After the warm baseline, 4 decode steps land in at most 2 kv chunks
    (plus the chunk carrying ``out``) — the handoff must ref everything
    else, mirroring tests/test_stream.py's delta assertions.
    """
    js, hosts, servers, router = _mk_fleet(tmp_path)
    try:
        rid = router.admit([7] * 8, 25, req_id="big", worker="s0")
        warm = router.warm(rid, "s1")
        # first copy: no cross-state baseline, so the only refs come from
        # intra-state dedup (the preallocated zero rows hash identically)
        assert warm["data_chunks"] + warm["ref_chunks"] == warm["chunks"]
        assert warm["data_chunks"] >= 3
        total_chunks = warm["chunks"]
        assert total_chunks >= 4  # the kv cache alone spans multiple chunks
        for _ in range(4):
            router.step()
        res = router.handoff(rid, "s1")
        assert res["warm"] is True
        assert res["chunks"] == total_chunks  # preallocated state: no growth
        assert res["data_chunks"] + res["ref_chunks"] == res["chunks"]
        # only the chunks the 4 new rows (+ out) landed in actually travel
        assert 1 <= res["data_chunks"] <= 3
        assert res["data_chunks"] < res["chunks"] / 2
        # and the adopted request finishes with the oracle's transcript
        router.run_to_completion()
        expected = run_reference(
            make_engine(SPEC),
            [{"id": "big", "prompt": [7] * 8, "max_new": 25}])
        assert router.transcript("big") == expected["big"]
        assert hosts["s1"].counters["prefills"] == 0  # zero re-prefill
        assert hosts["s1"].counters["migrations_in"] == 1
        assert hosts["s0"].counters["migrations_out"] == 1
    finally:
        _teardown(servers, router)


def test_concurrent_warm_baselines_do_not_clobber(tmp_path):
    """Two requests pre-copied to the SAME destination keep separate
    baselines (the fabric's relay cache is per-dest only; serve keys
    per (request, dest))."""
    js, hosts, servers, router = _mk_fleet(tmp_path, chunk_bytes=2048)
    try:
        a = router.admit([3] * 8, 20, req_id="a", worker="s0")
        b = router.admit([9] * 8, 20, req_id="b", worker="s0")
        router.warm(a, "s1")
        router.warm(b, "s1")
        for _ in range(3):
            router.step()
        ra = router.handoff(a, "s1")
        rb = router.handoff(b, "s1")
        for r in (ra, rb):
            assert r["warm"] is True
            assert r["ref_chunks"] >= 1  # each delta'd against ITS baseline
        router.run_to_completion()
        expected = run_reference(
            make_engine(SPEC),
            [{"id": "a", "prompt": [3] * 8, "max_new": 20},
             {"id": "b", "prompt": [9] * 8, "max_new": 20}])
        assert router.transcript("a") == expected["a"]
        assert router.transcript("b") == expected["b"]
    finally:
        _teardown(servers, router)


def test_stream_failure_falls_back_to_store(tmp_path):
    """Both live-migration legs armed to die -> publish + resume through
    the CAS store, transcripts unharmed, event records the fallback."""
    from repro.chaos import faults

    js, hosts, servers, router = _mk_fleet(tmp_path)
    try:
        expected = run_reference(make_engine(SPEC), REQS)
        for req in REQS:
            router.admit(req["prompt"], req["max_new"], req_id=req["id"])
            router.step()
        victim = next(r for r in sorted(router.pending())
                      if router.assignment[r] == "s0")
        with faults.arm({"point": "serve.migrate.mid_stream",
                         "action": "kill_conn", "times": 2}):
            event = router.migrate(victim, "s1")
        assert event["mode"] == "store"
        assert router.assignment[victim] == "s1"
        router.run_to_completion()
        for req in REQS:
            assert router.transcript(req["id"]) == expected[req["id"]]
        # the source forgot the request (no double-decode after fallback)
        assert victim not in hosts["s0"].active
    finally:
        _teardown(servers, router)


def test_finished_request_publishes_product(tmp_path):
    js, hosts, servers, router = _mk_fleet(tmp_path, names=("s0",))
    try:
        rid = router.admit([2, 4, 6], 5, req_id="p0")
        job_id = router.jobs[rid]
        router.run_to_completion()
        job = js.read_job(job_id)
        assert job.status == STATUS_FINISHED and job.product
        product, _ = restore_cmi(js.cmi_root(job_id), job.product)
        assert [int(t) for t in product["tokens"]] == router.transcript(rid)
    finally:
        _teardown(servers, router)


# ---------------------------------------------------------------------------
# spawned fleets: the headline + the notice path
# ---------------------------------------------------------------------------


@pytest.fixture
def fleet(tmp_path):
    from repro.fabric.supervisor import FabricSupervisor

    sup = FabricSupervisor(str(tmp_path / "s3"), str(tmp_path / "jobs"))
    try:
        yield sup, JobStore(tmp_path / "jobs")
    finally:
        sup.shutdown()


def _spawn(sup, router, names, *, publish_every=3):
    from repro.serve.scenarios import spawn_serve_worker

    for name in names:
        handle = spawn_serve_worker(sup, name, engine_spec=SPEC,
                                    publish_every=publish_every,
                                    chunk_bytes=4096)
        router.add_worker(name, handle.address)


def test_headline_migrate_then_sigkill_resume(fleet):
    """The PR's acceptance test: a 2-worker continuous-batching run where
    one in-flight request live-migrates mid-generation via a streamed delta
    hop (zero re-prefill, asserted on the destination's counters) and a
    SIGKILLed worker's requests resume from the last published CMI — all
    transcripts bit-identical to the unperturbed single-engine run."""
    sup, js = fleet
    router = ServeRouter(jobstore=js)
    expected = run_reference(make_engine(SPEC), REQS)
    try:
        _spawn(sup, router, ("s0", "s1"))
        for req in REQS:  # staggered joins
            router.admit(req["prompt"], req["max_new"], req_id=req["id"])
            router.step()

        victim = next(r for r in sorted(router.pending())
                      if router.assignment[r] == "s0")
        router.warm(victim, "s1")
        router.step()  # the warm copy goes stale by exactly this row
        event = router.migrate(victim, "s1", warm=False)
        assert event["mode"] == "stream"
        assert event["warm"] is True
        assert event["ref_chunks"] >= 1  # the delta actually delta'd
        assert event["data_chunks"] + event["ref_chunks"] == event["chunks"]
        status = router._call("s1", "svc/serve_status")
        assert status["counters"]["migrations_in"] == 1
        # zero re-prefill: s1 prefilled only the requests admitted TO it
        admitted_on_s1 = sum(
            1 for e in router.events
            if e["kind"] == "admit" and e["worker"] == "s1")
        assert status["counters"]["prefills"] == admitted_on_s1

        for _ in range(2):
            router.step()
        rc = sup.reclaim("s0", notice=False)  # SIGKILL: no flush, no notice
        assert rc == -signal.SIGKILL
        resumed = router.recover("s0", "s1")
        assert resumed  # something was actually stranded and came back
        router.run_to_completion()
        for req in REQS:
            assert router.transcript(req["id"]) == expected[req["id"]]
        # every serve job drove to finished on the survivor
        for job_id in router.jobs.values():
            assert js.read_job(job_id).status == STATUS_FINISHED
    finally:
        router.close()


def test_sigterm_notice_publishes_in_flight(fleet):
    """The 2-minute-notice path: SIGTERM -> publish-all -> EXIT_PREEMPTED;
    a resume on a fresh worker starts from the notice-time step (no decode
    loss at all, vs <= publish_every steps for SIGKILL)."""
    from repro.fabric.worker import EXIT_PREEMPTED

    sup, js = fleet
    router = ServeRouter(jobstore=js)
    expected = run_reference(make_engine(SPEC), REQS)
    try:
        _spawn(sup, router, ("s0",), publish_every=100)  # cadence never fires
        for req in REQS:
            router.admit(req["prompt"], req["max_new"], req_id=req["id"])
        for _ in range(4):
            router.step()
        done_at_notice = {
            rid: len(tr) for rid, tr in router.transcripts.items()}
        rc = sup.reclaim("s0", notice=True, wait_s=30)
        assert rc == EXIT_PREEMPTED

        _spawn(sup, router, ("s1",))
        resumed = router.recover("s0", "s1")
        assert set(resumed) == {r["id"] for r in REQS}
        # the notice-path publish captured the exact pre-SIGTERM position
        for e in router.events:
            if e["kind"] == "resume":
                assert e["done"] == done_at_notice[e["req"]]
        router.run_to_completion()
        for req in REQS:
            assert router.transcript(req["id"]) == expected[req["id"]]
    finally:
        router.close()


# ---------------------------------------------------------------------------
# launch CLI
# ---------------------------------------------------------------------------


def test_launch_cli_local_deterministic(capsys):
    from repro.launch import serve as launch

    m1 = launch.main(["--gen", "6", "--batch", "3", "--prompt-len", "5"])
    m2 = launch.main(["--gen", "6", "--batch", "3", "--prompt-len", "5"])
    assert m1["transcripts"] == m2["transcripts"]
    assert m1["prefill_tok_s"] > 0 and m1["decode_tok_s"] > 0
    assert "r000:" in capsys.readouterr().out


def test_launch_cli_routed_matches_local():
    from repro.launch import serve as launch

    local = launch.main(["--gen", "6", "--batch", "3", "--prompt-len", "5"])
    routed = launch.main(["--gen", "6", "--batch", "3", "--prompt-len", "5",
                          "--workers", "2"])
    assert routed["transcripts"] == local["transcripts"]
    assert "ttft_p50_s" in routed
