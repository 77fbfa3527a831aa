"""Mean ``dhp.publish`` of the window's checkpoint publishes of requests:
on admit, on the decode cadence and for a migration. Each stalls every
active request's token gap. Moves ``itl_p95_ms``."""

from benchmarks.chip.program_spans import mean, publishes, window


def read(run):
    recs = window(run)
    ms = [1e3 * r.duration for r in publishes(recs)] if recs else []
    return mean(ms)
