"""Pallas TPU kernels for the framework's compute hot-spots.

bitpack          pack/unpack 1-bit vote arrays (phase-1 wire format)
vote_pack        fused threshold-vote + pack (phase-1 wire in one pass)
vote_popcount    bit-plane unpack+popcount-accumulate (PS-side counting)
stoch_quant      fused scale + unbiased stochastic rounding (Eq. 1)
gather_quant     fused consensus select + quantize + residual (the whole
                 phase-2 client round in one d-pass, DESIGN.md §3)
flash_attention  VMEM-resident online-softmax attention (GQA/SWA) — the
                 TPU answer to the §Perf attention-tile traffic findings

Each kernel has a pure-jnp oracle (ref.py / models.attention).  Kernels
compile on a TPU and interpret elsewhere (platform.py); the tests check
them in interpret mode on the CPU and compile them for a described v5e.
"""

from . import (bitpack, flash_attention, gather_quant, ops, ref,  # noqa: F401
               stoch_quant, vote_pack, vote_popcount)
