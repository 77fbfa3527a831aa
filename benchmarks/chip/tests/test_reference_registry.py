"""The configuration file chooses its reference, and through it the program's
configuration and the operation counts: the qwen configurations map as they
always did, a stub reference in a package of its own is used wherever a run
calls one, and a configuration whose reference cannot serve the run is
refused before set-up."""

from __future__ import annotations

import sys
import uuid
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.chip import flops, harness, run
from benchmarks.chip.kinds import serve, train
from benchmarks.chip.tests import cpu  # noqa: F401  (puts the program on the path)

# The fields program_config sets on the registry entry for the qwen files,
# written out: a reference that maps them differently changes both cells.
QWEN = dict(d_model=2048, d_ff=6144, n_heads=16, n_kv_heads=8, head_dim=128, rope_theta=1e6,
            norm_eps=1e-6, tie_embeddings=True, attn_bias=False, dtype="bfloat16")
PARENT = {"qwen3-1.7b-train": dict(QWEN, n_layers=1, vocab=5427),
          "qwen3-1.7b-serve": dict(QWEN, n_layers=28, vocab=151936)}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_qwen_program_config_as_before(name):
    from repro.configs import get_config

    assert harness.program_config(harness.config(name)) == get_config("qwen3-1.7b").with_(**PARENT[name])


def test_qwen_counts_as_before():
    t, s = harness.config("qwen3-1.7b-train"), harness.config("qwen3-1.7b-serve")
    for c in (t, s):
        ref = harness.reference(c)
        assert (ref.train_step_flops, ref.decode_flops, ref.decode_bytes) == (
            flops.train_step_flops, flops.decode_flops, flops.decode_bytes)
    assert flops.train_step_flops(t, 4096, 24) == 41191420723200.0
    assert [(flops.decode_flops(s, p), flops.decode_bytes(s, p)) for p in (0, 255, 1151)] == [
        (3441131520.0, 3441264640.0), (3499622400.0, 3470510080.0), (3705143296.0, 3573270528.0)]


@pytest.mark.parametrize("workload", [w["name"] for w in harness.benchmark()["workloads"]])
def test_benchmark_cells_pass_the_look(workload):
    cell = harness.workload(workload)
    kind = harness.kind_for(cell)
    assert kind is {"train": train, "serve": serve}[harness.traffic(cell["traffic"])["kind"]]


BASE = '''
import numpy as np

CALLS = []


def make_params(c, seed, dtype):
    CALLS.append("make_params")
    return {"w": np.full(4, float(seed % 7 + 1), np.float32)}


def arch_fields(c):
    return {"n_layers": c["layers"], "d_model": c["width"]}
'''

TRAIN = '''

def flat(tree):
    return dict(tree)


def train_steps(c, opt, params0, batches, fmt=None):
    CALLS.append(("train_steps", fmt))
    k = 1.5 if fmt else 1.0
    return {"losses": [k, k, k], "first_grad": {"w": np.full(4, k)},
            "master": {"w": params0["w"] - 0.125 * k}}


def train_step_flops(c, seq_len, batch):
    return 1e12 * seq_len * batch
'''

SERVED = '''

def served_gaps(params, prompt, served, c, fmt=None):
    CALLS.append(("served_gaps", fmt))
    return dict({"served_gap": 0.25}, **({"control_gap": 0.75} if fmt else {}))
'''

DECODE = '''

def decode_flops(c, pos):
    return 1e9 * (pos + 1)


def decode_bytes(c, pos):
    return 2e9 * (pos + 1)
'''

# The stub references, by module name: all of the contract, and ones that lack part of it.
STUBS = {"stub": BASE + TRAIN + SERVED + DECODE, "no_served": BASE + TRAIN + DECODE,
         "no_counts": BASE + TRAIN + SERVED, "serve_only": BASE + SERVED + DECODE}


@pytest.fixture(scope="module")
def stubs(tmp_path_factory):
    """A package of references that only this module's tests know (``STUBS``)."""
    pkg = f"chip_stub_refs_{uuid.uuid4().hex[:8]}"
    root = tmp_path_factory.mktemp("refs")
    (root / pkg).mkdir()
    (root / pkg / "__init__.py").write_text("")
    for name, text in STUBS.items():
        (root / pkg / f"{name}.py").write_text(text)
    sys.path.insert(0, str(root))
    yield pkg
    sys.path.remove(str(root))
    for name in [m for m in sys.modules if m.split(".")[0] == pkg]:
        del sys.modules[name]


@pytest.fixture
def stub(stubs, monkeypatch):
    """The loader pointed at the stub package; the stub module, its calls cleared."""
    import importlib

    monkeypatch.setattr(harness, "REFERENCE_PACKAGE", stubs)
    mod = importlib.import_module(f"{stubs}.stub")
    mod.CALLS.clear()
    return mod


def stub_config(**kw) -> dict:
    c = {"name": "stub-1", "program": {"arch": "qwen3-1.7b"}, "reference": "stub",
         "layers": 3, "width": 96, "vocab_size": 50, "torch_dtype": "float32",
         "optimizer": {"b1": 0.9}, "check": {}}
    return dict(c, **kw)


def test_stub_maps_the_program_config(stub):
    from repro.configs import get_config

    assert harness.program_config(stub_config()) == get_config("qwen3-1.7b").with_(n_layers=3, d_model=96)


def test_stub_counts_are_run_flops(stub, monkeypatch):
    c = stub_config()
    assert harness.reference(c) is stub
    monkeypatch.setattr(harness, "metrics_for",
                        lambda cell, trace: [{"name": "train_step_mfu", "unit": "%"}])
    h = SimpleNamespace(config=c, traffic={"seq_len": 8, "batch": 2}, cell={"name": "stub-cell"},
                        trace=1, spans=harness.Spans())
    out = {"window": (0.0, 10.0), "setup_end": run.T_START + 1.0,
           "record": {"steps": [{"window": True, "first": False, "step_s": 2.0}]}}
    got = run.read_metrics(h, out, {"kind": "TPU v5 lite"}, None)
    peak = harness.peaks("TPU v5 lite")["bf16_flops"]
    assert got == {"train_step_mfu": {"value": 100.0 * 16e12 / 2.0 / peak, "unit": "%"}}


def test_stub_serves_the_serve_check(stub):
    done = {"finished_t": 1.0, "migrated_t": None, "resumed_ok": None, "prompt_len": 4,
            "max_new": 2, "prompt": np.arange(4), "tokens": [(0, 5), (1, 6)]}
    loop = SimpleNamespace(req={f"q{i}": dict(done, id=f"q{i}") for i in range(3)})
    h = SimpleNamespace(config=stub_config(), traffic={"check_requests": 2})
    out = {"loop": loop, "seed31": 3}
    assert serve.check(h, out) == {"served_gap": 0.25, "unfinished": 0, "torn": 0}
    assert serve.check(h, out, fmt="fp8")["served_gap"] == 0.75
    assert stub.CALLS == ["make_params", ("served_gaps", None), ("served_gaps", None),
                          "make_params", ("served_gaps", "fp8"), ("served_gaps", "fp8")]


def test_stub_serves_the_train_check(stub):
    h = SimpleNamespace(config=stub_config(), traffic={"seq_len": 8, "batch": 2})
    got = train.check(h, {"seed31": 5}, fmt="fp8")
    assert got == {"loss_gap": 0.5, "grad_gap": 0.5, "update_gap": 0.5, "excluded_leaves": []}
    assert stub.CALLS == ["make_params", ("train_steps", None), ("train_steps", "fp8")]


@pytest.mark.parametrize("reference, pointed, names", [
    ("no_such_ref", False, "benchmarks.chip.reference.no_such_ref"),
    ("no_such_ref", True, "no_such_ref"),
    ("no_served", True, "lacks served_gaps"),
    ("no_counts", True, "lacks decode_flops, decode_bytes"),
])
def test_unfit_reference_refused_at_start(stubs, monkeypatch, reference, pointed, names):
    if pointed:
        monkeypatch.setattr(harness, "REFERENCE_PACKAGE", stubs)
    c = dict(harness.config("qwen3-1.7b-serve"), reference=reference)
    cell = {"name": "serve-stub", "config": "qwen3-1.7b-serve", "traffic": "chat_migrate", "chips": 1}
    monkeypatch.setattr(harness, "workload", lambda name: cell)
    monkeypatch.setattr(harness, "config", lambda name: c)

    def no_setup(n):
        raise AssertionError("the run reached the chip's set-up")

    monkeypatch.setattr(harness, "require_chips", no_setup)
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "serve-stub", "--seed", str(2**31 + 77), "--seconds", "1"])
    msg = str(e.value.code)
    assert names in msg and "qwen3-1.7b-serve" in msg and "\n" not in msg


@pytest.mark.parametrize("workload, refused", [("serve-chat", None),
                                               ("train-ckpt", "lacks flat, train_steps, train_step_flops")])
def test_serve_only_reference_needs_no_training(stubs, monkeypatch, workload, refused):
    monkeypatch.setattr(harness, "REFERENCE_PACKAGE", stubs)
    c = dict(harness.config(harness.workload(workload)["config"]), reference="serve_only")
    monkeypatch.setattr(harness, "config", lambda name: c)
    if refused is None:
        assert harness.kind_for(harness.workload(workload)) is serve
    else:
        with pytest.raises(SystemExit, match=refused):
            harness.kind_for(harness.workload(workload))


def test_config_without_reference_refused(monkeypatch):
    c = {k: v for k, v in harness.config("qwen3-1.7b-train").items() if k != "reference"}
    monkeypatch.setattr(harness, "config", lambda name: c)
    with pytest.raises(SystemExit, match="no reference module"):
        harness.kind_for(harness.workload("train-ckpt"))

