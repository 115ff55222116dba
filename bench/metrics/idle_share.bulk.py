"""Device: share of the traced window in which no operation ran on the
chip (one minus the union of device-op intervals over the window)."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    return 100.0 * ctx.trace.idle_share()
