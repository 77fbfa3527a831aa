"""Routed experts one ``engine.decode`` call read, per MoE layer: the span's
``experts_read`` counter (the experts whose weights the step read, summed
over the MoE layers) over the configuration's MoE layers, averaged over the
window's calls. A step that reads only the held experts its token routed to
reads ``k * held / n_experts`` a layer on average (6 * 16 / 64 = 1.5 here);
one that reads every held expert reads all of them (16). Moves
``itl_p95_ms``: the experts' weights are most of a B=1 step's bytes."""

from benchmarks.chip.program_spans import named, window


def read(run):
    recs = window(run)
    reads = [r.attrs["experts_read"] for r in named(recs, "engine.decode")
             if "experts_read" in r.attrs] if recs else []
    if not reads:
        return None
    c = run.config
    return sum(reads) / len(reads) / (c["num_hidden_layers"] - c["first_k_dense_replace"])
