"""The published parameter count of a Mamba-2 configuration
(state-spaces/mamba2-130m, arXiv:2405.21060), from its file's numbers."""


def count(c: dict) -> int:
    """Mamba-2: embedding tied to the head."""
    inner = c["ssm_expand"] * c["d_model"]
    conv_dim = inner + 2 * c["ssm_groups"] * c["ssm_state"]
    layer = (c["d_model"] * (inner + conv_dim + c["ssm_heads"])   # in_proj
             + conv_dim * (c["conv_width"] + 1)                    # conv1d, bias
             + 3 * c["ssm_heads"]                                  # dt_bias, A_log, D
             + inner                                               # gated norm
             + inner * c["d_model"]                                # out_proj
             + c["d_model"])                                       # pre-norm
    return c["n_layers"] * layer + c["vocab"] * c["d_model"] + c["d_model"]
