"""Target model FLOP utilisation, in % of the chips' bf16 peak: the target
FLOPs of every delivered token (2 per weight it multiplies through, plus
attention over its context), over the window's wall time.  Draft work,
rejected verify tokens and padding do not count, so it cannot pass 100."""

from bench.harness import CLEAN


def read(ctx):
    if ctx.peak is None:
        return None
    cfg = ctx.config["target"]
    total = 0.0
    for s in ctx.streams:
        for r in s["requests"]:
            if r["reason"] not in CLEAN:
                continue
            p = len(r["prompt"])
            for j in range(len(r["output"])):
                total += ctx.flops.target_flops_per_token(cfg, p + j)
    return 100.0 * total / ctx.window_s / (ctx.chips
                                           * ctx.peak["bf16_flops_per_s"])
