"""Run the cells' kinds on the CPU at a tiny size, for the tests.

The harness's look for a chip is skipped; everything after it runs: the
program's training loop and serving hosts, the metric readers and the
comparison with the reference. The tiny configuration is the train and
serve configurations' own architecture with small widths, patched into the
program's registry for the test's duration.
"""

from __future__ import annotations

import copy
import time
from pathlib import Path
from types import SimpleNamespace

from benchmarks.chip import harness

harness.add_program_to_path()

TINY = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256}

# Rounding spreads with the width, so the numbers have limits of their own at
# the tiny size, set as the file's are (on the CPU): served gap, sound 0 to
# 0.0016, the fp8 control 0.032 to 0.058 (three seeds each); first gradient,
# sound 0.00095 to 0.0029, control 0.018 to 0.026, half the batch 0.085 to
# 0.28; update, sound 0.00025 to 0.00039, control 0.0053 to 0.0115, half the
# batch 0.016 to 0.063 (four seeds each).
TINY_CHECK = {"served_gap": 0.01, "grad_gap": 0.008, "update_gap": 0.002}


def tiny_config(name: str) -> dict:
    c = copy.deepcopy(harness.config(name))
    c.update(TINY)
    c["check"] = dict(c["check"], **{k: v for k, v in TINY_CHECK.items() if k in c["check"]})
    return c


def tiny_traffic(name: str) -> dict:
    tr = copy.deepcopy(harness.traffic(name))
    if tr["kind"] == "train":
        tr.update(seq_len=32, batch=4, publish_every=4 if tr["publish_every"] else 0,
                  reclaim_every=8 if tr["reclaim_every"] else 0)
    else:
        tr.update(rate_per_s=4.0, prompt_len={"12": 0.75, "20": 0.25},
                  output_len={"6": 0.75, "10": 0.25}, check_requests=4, drain_limit_s=30)
    return tr


def patch_registry(monkeypatch, c: dict) -> None:
    """The program's registry entry of ``c`` at the tiny widths."""
    import repro.configs as configs

    real = configs.get_config
    arch = c["program"]["arch"]

    def get_config(name):
        cfg = real(name)
        if name != arch:
            return cfg
        return cfg.with_(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                         d_ff=c["intermediate_size"], n_heads=c["num_attention_heads"],
                         n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                         vocab=c["vocab_size"])

    monkeypatch.setattr(configs, "get_config", get_config)


def context(config: dict, traffic: dict, work: Path, seed: int = 2**31 + 12345,
            seconds: float = 3.0, cell: str = "cpu-cell"):
    return SimpleNamespace(
        cell={"name": cell}, config=config, traffic=traffic, seed=seed, seconds=seconds,
        trace=0, work=work, trace_dir=work / "trace", spans=harness.Spans(),
        start_window=lambda: None, on_window_closed=lambda: None,
    )


def run_view(h, out) -> SimpleNamespace:
    """What the metric readers see, with the v5e's peaks."""
    t0, t1 = out["window"]
    return SimpleNamespace(out=out, config=h.config, traffic=h.traffic, t0=t0, t1=t1,
                           window_s=t1 - t0, setup_s=out["setup_end"] - time.perf_counter() + 1.0,
                           trace=None, peaks=harness.peaks("TPU v5 lite"),
                           flops=harness.reference(h.config), spans=h.spans.records)
