"""The Pallas TPU compiler params and the interpret-mode decision.

Kernels import ``CompilerParams`` from here (the ARCH001 rule keeps raw
``pltpu`` references inside ``src/repro/kernels/``), and default their
``interpret`` argument to ``None``, which ``resolve_interpret`` turns into
a bool: interpret on the CPU backend, compile everywhere else. A backend
that cannot be probed raises; it is never read as "no TPU".
"""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu

CompilerParams = pltpu.CompilerParams


def has_tpu_backend() -> bool:
    """True iff this process's default JAX backend is a TPU."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> interpret exactly when the default backend is the CPU;
    an explicit bool is passed through untouched (tests force ``True``)."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)
