"""Share of the bytes of the window's request publishes that were written to
the store (the publish counters ``written_bytes`` over ``bytes``): 100 when
each publish rewrites the whole cache, the share of new rows when it writes
only what changed. Moves ``itl_p95_ms``."""

from benchmarks.chip.program_spans import publishes, window


def read(run):
    recs = window(run)
    pubs = [r.attrs for r in publishes(recs) if "bytes" in r.attrs] if recs else []
    total = sum(a["bytes"] for a in pubs)
    return 100.0 * sum(a["written_bytes"] for a in pubs) / total if total else None
