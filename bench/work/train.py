"""Required work of one trainer round on one chip (one client), from its
shapes.

``ops``: the forward and backward passes' operations, without the
recomputation the program does to save memory: 6 operations a parameter
a token for the matrix products (the tied output head among them), and
the SSD's own products, counted at the configuration's chunk ``Q``: the
causal half of the within-chunk scores ``C_t . B_s`` (per B/C group) and
of their weighted sum of ``x_s`` (per head), the chunk state's update
and its read-out (``N * P`` a head each), and the depthwise conv, each
a multiply and an add, times 3 for the backward pass. A client runs
``local_steps`` steps on ``seqs_per_client * seq_len`` tokens a round.

``bytes``: the least the aggregation moves, counted as the caller holds
its state: read the float32 update and the residual, write the new
residual, and read and write the parameters (d each).
"""

F32 = 4


def parameters(c: dict) -> int:
    din = c["ssm_heads"] * c["ssm_head_dim"]
    gn = c["ssm_groups"] * c["ssm_state"]
    layer = (c["d_model"] * (2 * din + 2 * gn + c["ssm_heads"])   # W_x, W_z, W_B, W_C, W_dt
             + din * c["d_model"]                                # out_proj
             + c["conv_width"] * (din + 2 * gn)                  # conv, no bias
             + 3 * c["ssm_heads"] + din + c["d_model"])          # A_log, D, dt_bias; norms
    return c["n_layers"] * layer + c["vocab"] * c["d_model"] + c["d_model"]


def required(c: dict, traffic: dict) -> dict:
    din = c["ssm_heads"] * c["ssm_head_dim"]
    gn = c["ssm_groups"] * c["ssm_state"]
    q = c["ssm_chunk"]
    matmul = (c["n_layers"] * (c["d_model"] * (2 * din + 2 * gn + c["ssm_heads"])
                               + din * c["d_model"])
              + c["vocab"] * c["d_model"])
    ssd = c["n_layers"] * ((q + 1) / 2 * (gn + din) + 2 * c["ssm_state"] * din
                           + c["conv_width"] * (din + 2 * gn))
    tokens = traffic["local_steps"] * traffic["seqs_per_client"] * traffic["seq_len"]
    return {"ops": 6 * tokens * (matmul + ssd), "bytes": 5 * parameters(c) * F32}
