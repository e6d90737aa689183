"""Where the Pallas kernels run: compiled for the chip, interpreted elsewhere.

Every kernel, ops wrapper and index entry point takes ``interpret=None``
and resolves it here, so the choice is made in one place: Mosaic kernels
compile only for a TPU, so any other default backend (the CPU test runs)
executes them in the Pallas interpreter. Passing ``interpret=False``
explicitly forces the Mosaic lowering — how the compile tests build chip
programs from a CPU host for a described TPU topology.
"""

from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """``interpret`` itself when given; else True unless the default
    backend is a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
