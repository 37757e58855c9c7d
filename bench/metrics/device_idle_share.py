"""Share of the traced window in which the fullest device ran no op."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["fullest_busy_s"] / tr["window_s"])
