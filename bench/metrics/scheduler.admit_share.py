"""Share of a stream's wall time spent in admission, in %: sum of
StepReport.admit_time (the admission prefills' dispatch and the
first-token fetch that waits on them) / WaveReport.wall_time, over one
more stream of the window's requests (bench/program_stream.py).
scheduler.outside_round_share minus this is the scheduler's other host
work between rounds."""
from bench import program_stream


def read(ctx):
    rec = program_stream.record(ctx)
    if rec is None or rec["wall_s"] <= 0:
        return None
    return 100.0 * rec["admit_s"] / rec["wall_s"]
