"""Mean wall time of one ``engine.decode`` call (one request, one token: the
jitted step, the argmax and its sync to the host). Moves ``itl_p95_ms``."""

from benchmarks.chip.program_spans import mean_duration, window


def read(run):
    s = mean_duration(window(run), "engine.decode")
    return None if s is None else 1e3 * s
