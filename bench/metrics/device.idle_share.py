"""Share of the traced stream in which no operation runs on the device, in
%: 1 - (union of device op intervals) / traced window, averaged over the
chips used (profiler trace)."""


def read(ctx):
    t = ctx.trace
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
