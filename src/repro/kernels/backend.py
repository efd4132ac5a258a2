"""Where the Pallas kernels run: compiled on a TPU, interpreted elsewhere.

The choice follows the platform the kernel is called on, never a user
setting, so a TPU never runs the interpreter and a CPU never asks the TPU
compiler for a kernel.  Tests and compile checks that must steer it pass
``interpret=`` to the kernel explicitly.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret`` as given, or — for ``None`` — whether the default
    backend lacks the Mosaic TPU compiler (anything but ``"tpu"``)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
