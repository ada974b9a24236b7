"""device_idle_share: 1 − (union of the device's op intervals) over the
traced window, in % (layer: device)."""


def read(ctx):
    t = ctx.trace
    if not t or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
