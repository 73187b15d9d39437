"""Share of the traced window in which no operation ran on the device, in %
(1 minus the union of device op intervals over the window, averaged over
the chips) - the device."""


def read(ctx):
    s = ctx.summary
    if s is None or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
