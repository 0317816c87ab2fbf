"""Share (%) of the traced window in which no op ran on the device: 1 -
busy / window, busy being the union of the device's op intervals."""


def read(ctx):
    w = ctx.device.get("window_s")
    if not w or not ctx.device.get("busy_s"):
        return None
    return 100.0 * (1.0 - ctx.device["busy_s"] / w)
