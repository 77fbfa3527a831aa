"""Seconds JAX spent compiling inside the window (``jax.compile`` records);
0.0 when nothing compiled. A shape the warm-up missed shows here. Moves
``itl_p95_ms``."""

from benchmarks.chip.program_spans import compile_seconds as read  # noqa: F401
