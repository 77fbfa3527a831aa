"""Trace-time sharding-constraint context.

Perf iterations steer GSPMD with `with_sharding_constraint` at a few
well-chosen points (the residual stream). The model code
stays mesh-agnostic: it calls ``constrain(x, kind)`` and the step builder
installs concrete NamedShardings for each kind before tracing.

Kinds:
  resid    — (B, S, E) residual stream between layers
             (seq-parallel hillclimb: P(batch, "model", None))
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Any, Mapping

import jax

_CONSTRAINTS: contextvars.ContextVar[Mapping[str, Any] | None] = contextvars.ContextVar(
    "sharding_constraints", default=None
)


@contextlib.contextmanager
def sharding_context(constraints: Mapping[str, Any]):
    token = _CONSTRAINTS.set(dict(constraints))
    try:
        yield
    finally:
        _CONSTRAINTS.reset(token)


def constrain(x: jax.Array, kind: str) -> jax.Array:
    c = _CONSTRAINTS.get()
    if not c or kind not in c:
        return x
    return jax.lax.with_sharding_constraint(x, c[kind])
