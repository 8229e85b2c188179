"""Pallas TPU kernels for the hot spots of the elastic round.

Every kernel entry point takes ``interpret: bool | None = None``; ``None``
resolves through :func:`interpret_mode`, so a caller that leaves the
argument out gets the compiled kernel on a TPU and the Pallas interpreter
everywhere else, never the interpreter on the chip.
"""
from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """Resolve a kernel's ``interpret`` flag: an explicit bool wins;
    ``None`` means interpret exactly when the default backend is not a
    TPU."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"
