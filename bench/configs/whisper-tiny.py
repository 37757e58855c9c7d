"""The published parameter count of a Whisper configuration
(openai/whisper-tiny, arXiv:2212.04356), from its file's numbers."""


def count(c: dict) -> int:
    """Whisper (openai/whisper-tiny): biased q, v, out; LayerNorms with
    biases; a two-matrix MLP with biases; head tied to the embedding."""
    m, ff = c["d_model"], c["d_ff"]
    attn = 4 * m * m + 3 * m
    mlp = 2 * m * ff + ff + m
    ln = 2 * m
    conv = (c["num_mel_bins"] * m * c["conv_width"] + m
            + m * m * c["conv_width"] + m)
    enc = conv + c["source_len"] * m + c["encoder_layers"] * (attn + mlp + 2 * ln) + ln
    dec = (c["vocab"] * m + c["decoder_len"] * m
           + c["n_layers"] * (2 * attn + mlp + 3 * ln) + ln)
    return enc + dec
