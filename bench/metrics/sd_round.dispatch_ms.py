"""Mean host time from an SD round's call until its programs are
dispatched, in ms: StepReport.dispatch_time (the program's host clock: key
split, ``active`` upload and the jitted round's dispatch), over the rounds
of one more stream of the window's requests (bench/program_stream.py).
The device may sit idle through it."""
from bench import program_stream


def read(ctx):
    rec = program_stream.record(ctx)
    if rec is None or not rec["rounds"]:
        return None
    t = [r["dispatch_time"] for r in rec["rounds"]]
    return 1e3 * sum(t) / len(t)
