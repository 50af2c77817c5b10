"""90th percentile, over every request of the window, of its normalised
latency: (finish time - send time) / output tokens, in ms (host clock).
Prefill, admission stalls and decode pace count together.  A request that
did not finish cleanly counts as missing: an infinite latency."""
import numpy as np

from bench.harness import CLEAN


def read(ctx):
    v = []
    for s in ctx.streams:
        for r in s["requests"]:
            if r["reason"] in CLEAN and len(r["output"]):
                v.append((r["finished_at"] - r["sent_at"]) * 1e3
                         / len(r["output"]))
            else:
                v.append(float("inf"))
    p90 = float(np.percentile(np.asarray(v), 90))
    ctx.note(f"ms_per_token_p90: {len(v)} samples, median "
             f"{float(np.median(v)):.4f} ms, p90 {p90:.4f} ms")
    return p90 if np.isfinite(p90) else None
