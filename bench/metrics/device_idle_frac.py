"""Share of the traced window in which no operation runs on the device,
in %: 1 - (union of device-op intervals) / window, averaged over chips."""


def read(ctx):
    window = ctx.trace.window_s
    if window <= 0 or not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / window)
