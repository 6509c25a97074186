"""1 - the union of device busy intervals over the traced window, in %."""


def read(ctx):
    busy, window = ctx.trace.busy_in_window()
    if not ctx.trace.ops or window <= 0:
        return None
    return (1.0 - busy / window) * 100
