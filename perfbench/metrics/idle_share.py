"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    s = ctx.get("trace")
    if not s or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
