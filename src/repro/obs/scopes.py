"""Device scopes: the names of a round's phases in the compiled program
(DESIGN.md §15).

A scope is ``jax.named_scope``: trace-time metadata that XLA carries into
every HLO instruction's ``op_name`` (``jit(f)/phase2/while/body/...``) and
the TPU profiler reports with each device op (the ``tf_op`` stat), so a
trace can be split by phase without reading HLO numbering. Scopes change
no jaxpr equation, no buffer and no output.

Every scope the program opens is named here, and :func:`scope` refuses
any other name, so a reader of a trace and the program agree on one
list. The monolithic, stream and sharded engines open the first four:

* ``vote`` — phase 1: the vote keys, each client's vote and the vote
  counts, and the clients' max |u| the scale factor needs;
* ``consensus`` — the scale factor and the consensus selection (the
  round plan, or the sharded engine's histogram and slots);
* ``phase2`` — compaction, quantization, the residual write-back and the
  fold, and the delta;
* ``register_fold`` — the switch's integer fold of the client axis
  (``robust_agg.client_sum``), inside ``phase2``;

and the FL loop opens ``local_train`` around every client's local
training.
"""

from __future__ import annotations

import jax

__all__ = ["SCOPES", "scope"]

SCOPES = ("vote", "consensus", "phase2", "register_fold", "local_train")


def scope(name: str):
    """``jax.named_scope(name)`` for a name in :data:`SCOPES`."""
    if name not in SCOPES:
        raise ValueError(f"unknown device scope {name!r} "
                         f"(expected one of {', '.join(SCOPES)})")
    return jax.named_scope(name)
