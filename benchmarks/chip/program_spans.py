"""What the readers of the program's own spans share.

The program records its spans in memory (``repro.spans``, the recorder of
the process that ran the cell). A reader takes the spans that lie wholly
inside the window, ``[run.t0, run.t1]``, and reads nothing (``None``) where
the program has no recorder, where the ring has already dropped a span of
the window, or where the window holds none.
"""

from __future__ import annotations

PUBLISH = "dhp.publish"
CKPT = "ckpt"


def window(run) -> list | None:
    """The program's spans inside the run's window, or ``None``."""
    try:
        from repro import spans
    except ImportError:  # a program without the recorder
        return None
    if spans.truncated(run.t0):
        return None
    return spans.records(run.t0, run.t1) or None


def named(recs: list, name: str) -> list:
    return [r for r in recs if r.name == name]


def publishes(recs: list) -> list:
    """The window's checkpoint publishes (``dhp.publish``, status ``ckpt``)."""
    return [r for r in named(recs, PUBLISH) if r.attrs.get("status") == CKPT]


def mean(xs: list) -> float | None:
    return sum(xs) / len(xs) if xs else None


def mean_duration(recs: list | None, name: str) -> float | None:
    """Mean seconds of the spans called ``name``."""
    return mean([r.duration for r in named(recs, name)]) if recs else None


def per_publish(run, phases: set) -> float | None:
    """Mean over the window's publishes of the seconds their ``phases``
    spans (any depth below the publish) took."""
    recs = window(run)
    if recs is None:
        return None
    kids: dict = {}
    for r in recs:
        kids.setdefault(r.parent, []).append(r)

    def inside(root) -> float:
        total, todo = 0.0, list(kids.get(root.id, ()))
        while todo:
            r = todo.pop()
            total += r.duration if r.name in phases else 0.0
            todo.extend(kids.get(r.id, ()))
        return total

    return mean([inside(p) for p in publishes(recs)])


def compile_seconds(run) -> float | None:
    """Seconds in the window in which JAX was compiling: the union of the
    ``jax.compile`` records (nested traces overlap); 0.0 when none ran."""
    recs = window(run)
    if recs is None:
        return None
    total, end = 0.0, float("-inf")
    for r in sorted(named(recs, "jax.compile"), key=lambda r: r.start):
        if r.end > end:
            total += r.end - max(r.start, end)
            end = r.end
    return total
