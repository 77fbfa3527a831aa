"""The readers of the program's own spans (``repro.spans``): on a synthetic
window, outside it, after the ring dropped part of it, without the recorder,
and on each cell's run on the CPU at a tiny size."""

from __future__ import annotations

import math
import sys
from collections import deque
from types import SimpleNamespace

import pytest

from benchmarks.chip import harness
from benchmarks.chip.tests import cpu

harness.add_program_to_path()

from repro import spans  # noqa: E402
from repro.spans import span  # noqa: E402

TRAIN = ["publish_d2h_s", "publish_hash_wait_s", "publish_io_s", "restore_s", "compile_s.train"]
SERVE = ["serve_publish_ms", "publish_written_share.serve", "decode_call_ms", "compile_s.serve"]


def rec(i, name, start, end, parent=None, **attrs):
    r = span(name, **attrs)
    r.start, r.end, r.id, r.parent, r.thread = start, end, i, parent, 0
    return r


def synthetic() -> list[span]:
    """A window [100, 200]: two checkpoint publishes inside it, one before
    it, a finished one; two restarts; decode calls; nested compiles."""
    return [
        # before the window: never read
        rec(1, "dhp.publish", 90.0, 95.0, status="ckpt", bytes=10, written_bytes=10),
        rec(2, "ckpt.d2h", 90.0, 94.0, 1),
        # publish 1 at [110, 112]: d2h 0.5 in two copies, hash 0.3, io 0.2+0.1+0.1
        rec(10, "ckpt.d2h", 110.0, 110.25, 11),
        rec(12, "ckpt.d2h", 110.3, 110.55, 11),
        rec(13, "ckpt.hash_wait", 110.6, 110.9, 11),
        rec(14, "ckpt.write_wait", 111.0, 111.2, 11),
        rec(15, "ckpt.commit", 111.2, 111.3, 11),
        rec(11, "dhp.publish", 110.0, 112.0, None, job="j", step=8, status="ckpt",
            bytes=1000, written_bytes=400),
        rec(16, "jobstore.record", 111.4, 111.5, 18),  # under a nested span
        rec(18, "t.between", 111.35, 111.6, 11),
        # publish 2 at [150, 151]: d2h 0.1, hash 0.1, io 0.1
        rec(20, "ckpt.d2h", 150.0, 150.1, 21),
        rec(22, "ckpt.hash_wait", 150.1, 150.2, 21),
        rec(23, "jobstore.record", 150.3, 150.4, 21),
        rec(21, "dhp.publish", 150.0, 151.0, None, job="j", step=16, status="ckpt",
            bytes=1000, written_bytes=100),
        # a finished publish: not a checkpoint
        rec(30, "dhp.publish", 160.0, 160.5, None, status="finished"),
        rec(31, "dhp.restart", 120.0, 121.0, job="j"),
        rec(32, "dhp.restart", 170.0, 173.0, job="j"),
        rec(33, "engine.decode", 130.0, 130.02),
        rec(34, "engine.decode", 131.0, 131.04),
        # compiles: [140, 142] with a nested [140.5, 141], and [145, 145.5]
        rec(40, "jax.compile", 140.5, 141.0, phase="jaxpr_trace"),
        rec(41, "jax.compile", 140.0, 142.0, phase="jaxpr_trace"),
        rec(42, "jax.compile", 145.0, 145.5, phase="backend_compile"),
        # straddles the close: never read
        rec(50, "dhp.restart", 199.0, 201.0),
    ]


@pytest.fixture
def ring(monkeypatch):
    r = deque(synthetic(), maxlen=spans.RING)
    monkeypatch.setattr(spans, "_ring", r)
    monkeypatch.setattr(spans, "_dropped_start", float("-inf"))
    return r


def read(name, t0=100.0, t1=200.0):
    return harness.reader(name)(SimpleNamespace(t0=t0, t1=t1))


WANT = {
    "publish_d2h_s": (0.5 + 0.1) / 2,
    "publish_hash_wait_s": (0.3 + 0.1) / 2,
    "publish_io_s": (0.2 + 0.1 + 0.1 + 0.1) / 2,
    "restore_s": (1.0 + 3.0) / 2,
    "compile_s.train": 2.0 + 0.5,
    "serve_publish_ms": 1e3 * (2.0 + 1.0) / 2,
    "publish_written_share.serve": 100.0 * 500 / 2000,
    "decode_call_ms": 1e3 * (0.02 + 0.04) / 2,
    "compile_s.serve": 2.0 + 0.5,
}


@pytest.mark.parametrize("name", TRAIN + SERVE)
def test_reader_on_a_synthetic_window(ring, name):
    assert read(name) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", TRAIN + SERVE)
def test_reader_reads_nothing_outside_the_window(ring, name):
    assert read(name, 300.0, 400.0) is None


@pytest.mark.parametrize("name", TRAIN + SERVE)
def test_reader_reads_nothing_after_the_ring_dropped_part_of_it(ring, monkeypatch, name):
    monkeypatch.setattr(spans, "_dropped_start", 150.0)
    assert read(name) is None
    monkeypatch.setattr(spans, "_dropped_start", 99.0)  # dropped before the window
    assert read(name) is not None


@pytest.mark.parametrize("name", TRAIN + SERVE)
def test_reader_reads_nothing_from_a_program_without_the_recorder(ring, monkeypatch, name):
    import repro

    monkeypatch.delattr(repro, "spans")
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    assert read(name) is None


def test_compile_seconds_are_zero_in_a_window_without_compiles(ring):
    assert read("compile_s.train", 100.0, 139.0) == 0.0


def test_train_cell_reports_each_program_span_metric(monkeypatch, tmp_path):
    c, tr = cpu.tiny_config("qwen3-1.7b-train"), cpu.tiny_traffic("spot_ckpt")
    cpu.patch_registry(monkeypatch, c)
    h = cpu.context(c, tr, tmp_path, seconds=2.0)
    from benchmarks.chip.kinds import train

    out = train.run(h)
    run = cpu.run_view(h, out)
    got = {m: harness.reader(m)(run) for m in TRAIN}
    assert all(v is not None and math.isfinite(v) for v in got.values()), got
    stall = harness.reader("publish_stall_s")(run)
    parts = got["publish_d2h_s"] + got["publish_hash_wait_s"] + got["publish_io_s"]
    assert 0 < parts <= stall, (got, stall)


def test_serve_cell_reports_each_program_span_metric(monkeypatch, tmp_path):
    c, tr = cpu.tiny_config("qwen3-1.7b-serve"), cpu.tiny_traffic("chat_migrate")
    cpu.patch_registry(monkeypatch, c)
    h = cpu.context(c, tr, tmp_path, seconds=3.0)
    from benchmarks.chip.kinds import serve

    out = serve.run(h)
    run = cpu.run_view(h, out)
    got = {m: harness.reader(m)(run) for m in SERVE}
    assert all(v is not None and math.isfinite(v) for v in got.values()), got
    assert 0 < got["publish_written_share.serve"] <= 100.0
