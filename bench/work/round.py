"""Required work of one FediAC round, from its shapes.

A lower bound on what any correct implementation moves, counted as the
caller holds the round's inputs and outputs, with the harness's
``g + residual`` add fused in: read the float32 [N, d] local updates and
the previous float32 [N, d] residual, write the new residual and the
d-long float32 delta. The arithmetic (a few operations a coordinate) is
negligible next to the bytes, so ``ops`` is 0 and the bound is memory.
"""

F32 = 4


def required(n: int, d: int) -> dict:
    return {"ops": 0, "bytes": (3 * n + 1) * d * F32}
