"""90th percentile, over the requests of one more stream of the window's
requests (bench/program_stream.py), of the time from a request's send
(loadgen.send_times, as ms_per_token_p90 has it) until its first token
reaches the host (Request.first_token_at, after the admission prefill's
fetch), in ms.  A request without a first token counts as infinite."""
import numpy as np

from bench import program_stream


def read(ctx):
    rec = program_stream.record(ctx)
    if rec is None or not rec["first_token_s"]:
        return None
    v = [float("inf") if s is None else 1e3 * s
         for s in rec["first_token_s"]]
    p90 = float(np.percentile(np.asarray(v), 90))
    ctx.note(f"first token: {len(v)} requests, median "
             f"{float(np.median(v)):.4f} ms, p90 {p90:.4f} ms")
    return p90 if np.isfinite(p90) else None
