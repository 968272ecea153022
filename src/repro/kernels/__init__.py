"""Pallas TPU kernels.

Layout per the repo convention: <name>.py holds the pl.pallas_call +
BlockSpec tiling; ops.py the jit'd wrappers (+ planner region registration);
ref.py the pure-jnp oracles.

Every kernel takes ``interpret: bool | None``.  ``None`` (the default)
compiles the kernel with Mosaic on a TPU backend and runs it in the Pallas
interpreter on any other backend; it is resolved when the kernel is traced,
never at import.  An explicit bool overrides that."""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """Interpret mode for a kernel call: the explicit value when given,
    otherwise on exactly when the default backend is not a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
