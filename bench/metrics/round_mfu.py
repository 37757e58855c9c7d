"""The whole round's share of the chip's peak: the least time the round's
required work takes at the published peaks (the larger of operations
over the peak rate and bytes over the HBM bandwidth) over the measured
time of a round in the traced window."""


def read(ctx):
    tr, work, peaks = ctx.get("trace"), ctx["work"], ctx["peaks"]
    if not tr or not tr["rounds"] or tr["window_s"] <= 0:
        return None
    least = max(work["ops"] / peaks["bf16_flops_per_s"],
                work["bytes"] / peaks["hbm_bytes_per_s"])
    if least <= 0:
        return None
    return 100.0 * least / (tr["window_s"] / tr["rounds"])
