"""The program's own per-round and per-request records, from one more
untraced stream of the window's requests.

The harness keeps of each window stream only what the first metrics read
(``harness.serve_stream``), so the readers of the program's later fields
(``StepReport.dispatch_time`` / ``admit_time`` / ``experts_reached``,
``Request.first_token_at``) serve this stream themselves, once per run:
after the traced stream, on the same engine, with the same request set.
Every stream of a run serves the same requests greedily from an empty
pool, so its rounds, their batches and their routing are those of every
window stream and of the traced stream, round for round; only host
timings differ, by noise.  The record stands beside the traced stream,
so it is served only in a run whose trace holds a device window
(``ctx.trace``; a run off the chip has none).  A program without these
fields gives None and serves nothing either.
"""
from __future__ import annotations

import time
from typing import Optional

from bench import loadgen

#: fields the program records; all of them, or the readers read nothing
STEP_FIELDS = ("dispatch_time", "admit_time", "experts_reached")
REQUEST_FIELDS = ("admitted_at", "first_token_at")


def recorded() -> bool:
    """True when the program's records carry every field read here."""
    from repro.serving.engine import Request
    from repro.serving.scheduler import StepReport
    return (all(f in StepReport.__dataclass_fields__ for f in STEP_FIELDS)
            and all(f in Request.__dataclass_fields__
                    for f in REQUEST_FIELDS))


def serve(eng, reqs, clients: int) -> dict:
    """Serve ``reqs`` as one closed-loop stream; the program's records.

    ``rounds``: per SD round (``StepReport.live`` > 0), in order, its
    gamma, ``dispatch_time`` and ``experts_reached``; ``admit_s``: the
    stream's summed ``admit_time``; ``wall_s``: the stream's
    ``WaveReport.wall_time``; ``first_token_s``: per request, first token
    on the host minus its send time (``loadgen.send_times``), None for a
    request that never had one."""
    t_start = time.perf_counter()
    uids = [eng.submit(r.prompt, r.max_new) for r in reqs]
    reports = eng.run()
    done = [eng.done.pop(u) for u in uids]
    eng.reports.clear()
    sends = loadgen.send_times(t_start, [r.finished_at for r in done],
                               clients)
    steps = [s for rep in reports for s in (rep.steps or [])]
    return {
        "wall_s": sum(rep.wall_time for rep in reports),
        "admit_s": sum(s.admit_time for s in steps),
        "rounds": [{"gamma": s.gamma, "dispatch_time": s.dispatch_time,
                    "experts_reached": s.experts_reached}
                   for s in steps if s.live > 0],
        "first_token_s": [None if r.first_token_at is None
                          else r.first_token_at - sent
                          for r, sent in zip(done, sends)],
    }


def record(ctx) -> Optional[dict]:
    """The run's program record (``serve``), served on the first call and
    kept on ``ctx``; None without a device trace or when the program
    lacks the fields."""
    rec = getattr(ctx, "program_stream", None)
    if rec is None:
        if ctx.trace is None or not recorded():
            return None
        reqs = loadgen.request_set(ctx.traffic, ctx.seed,
                                   ctx.config["target"]["vocab_size"])
        t = time.perf_counter()
        rec = serve(ctx.engine, reqs, ctx.traffic["clients"])
        ctx.note(f"program record: one more stream, {len(rec['rounds'])} "
                 f"rounds, {time.perf_counter() - t:.3f} s")
        ctx.program_stream = rec
    return rec


def moe_layers(cfg) -> int:
    """Routed-MoE layers of a program ``ModelConfig``."""
    return sum(cfg.moe_pattern) * cfg.num_periods
