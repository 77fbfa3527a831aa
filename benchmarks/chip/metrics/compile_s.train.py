"""Seconds JAX spent compiling inside the window (``jax.compile`` records:
tracing, lowering, backend compile or a persistent-cache load); 0.0 when
nothing compiled. A resume that re-jits shows here. Moves
``train_goodput_tok_s``."""

from benchmarks.chip.program_spans import compile_seconds as read  # noqa: F401
