"""Interpret mode is the caller's decision, never the kernel wrapper's.

The engine decides once, at construction, from ``EngineConfig.interpret``
(CPU tests and dry runs say ``interpret=True``). A wrapper that switched
itself to the interpreter whenever the backend was not a TPU would let a
serving process that lost its chip keep answering from the CPU, and a
benchmark report interpreter timings under a device's name.
"""

from __future__ import annotations

import jax


def require_tpu_unless_interpret(kernel: str, interpret: bool) -> None:
    """Raise when a Mosaic-compiled kernel is requested off-TPU."""
    backend = jax.default_backend()
    if not interpret and backend != "tpu":
        raise RuntimeError(
            f"{kernel}: a compiled Pallas kernel was requested on the "
            f"{backend!r} backend; Mosaic needs a TPU. Pass interpret=True "
            "(EngineConfig.interpret / INTERPRET=1) for CPU tests and dry "
            "runs — the wrapper does not fall back on its own."
        )
