"""Where the Pallas kernels run: compiled for the TPU, or through the Pallas
interpreter when the default backend is the CPU (the test suite's lane).

This is the one place the choice is made.  ``KernelConfig.resolve()``
(``models/layers.py``) calls it once when an engine is built and threads
the answer to every kernel; a kernel called directly with
``interpret=None`` asks it too.
"""
from __future__ import annotations

import jax


def default_interpret() -> bool:
    """True only on the CPU backend: a TPU never runs a kernel interpreted."""
    return jax.default_backend() == "cpu"


def resolve_interpret(interpret: bool | None) -> bool:
    return default_interpret() if interpret is None else bool(interpret)
