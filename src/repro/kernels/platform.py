"""Where a Pallas kernel runs: compiled on a TPU, interpreted elsewhere.

Every kernel takes ``interpret: bool | None = None``; ``None`` resolves
here, from the backend the process runs on, so a caller on the chip never
interprets by accident and a CPU caller never asks Mosaic for a TPU
lowering.  Pass ``interpret=False`` explicitly to compile for a described
(not attached) TPU topology.
"""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret`` as given, or ``True`` unless the backend is a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
