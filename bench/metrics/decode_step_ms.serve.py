"""Mean host-clock time of `Server.decode_step` over the window's steps
(the serve loop's watchdog hook): total time over count, in ms."""


def read(ctx):
    times = ctx.decode_times()
    return sum(times) / len(times) * 1e3 if times else None
