"""Share of the streams' wall time spent outside SD rounds, in %:
1 - sum(StepReport.round_time) / sum(stream wall time), over the window.
The rest is the scheduler's host work between rounds: admissions and
their prefills, page growth, bookkeeping."""


def read(ctx):
    rounds = sum(sum(s["round_times"]) for s in ctx.streams)
    wall = sum(s["report_wall_s"] for s in ctx.streams)
    return 100.0 * (1.0 - rounds / wall) if wall > 0 else None
