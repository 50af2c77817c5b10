"""Output tokens of the window's requests that finished cleanly (length or
eos), over the whole window's wall time (host clock)."""

from bench.harness import CLEAN


def read(ctx):
    n = sum(len(r["output"]) for s in ctx.streams for r in s["requests"]
            if r["reason"] in CLEAN)
    return n / ctx.window_s
