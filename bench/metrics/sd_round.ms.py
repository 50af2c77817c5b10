"""Mean SD round time over the window's rounds, in ms: StepReport.round_time,
the program's host clock from dispatch to the round's one device_get."""


def read(ctx):
    t = [x for s in ctx.streams for x in s["round_times"]]
    return 1e3 * sum(t) / len(t) if t else None
