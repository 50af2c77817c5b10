"""Mean number of distinct experts a target MoE layer runs per SD verify:
StepReport.experts_reached (the program's count over every row the ragged
gmm processes, padding rows included, summed over the MoE layers) / MoE
layers, averaged over the rounds of one more stream of the window's
requests (bench/program_stream.py).  The experts whose weights a verify
reads."""
from bench import program_stream


def read(ctx):
    rec = program_stream.record(ctx)
    if rec is None or not rec["rounds"]:
        return None
    n = [r["experts_reached"] for r in rec["rounds"]]
    if any(x is None for x in n):
        return None
    layers = program_stream.moe_layers(ctx.engine.target.cfg)
    return sum(n) / len(n) / layers
