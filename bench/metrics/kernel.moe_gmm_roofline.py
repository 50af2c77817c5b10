"""The ragged gmm's share of its roofline in the traced window, in %.

Numerator: per SD round in the window and per MoE layer, the least time
of its two launches (``flops.gmm_calls``: the fused gate+up and the down
over B * (gamma + 1) * top_k routed rows; bytes the weights of the
experts the round's verify reached, StepReport.experts_reached spread
evenly over the layers, plus the rows read and written), summed.
Denominator: the device time of the ``ragged_moe_ffn`` ops of
``jit_round_fn`` that start in the window (device trace).

The window holds whole rounds from the traced stream's first: its rounds
are the ``bench.round`` spans in it, and their experts come from the
program record (bench/program_stream.py), whose rounds are the traced
stream's, round for round.  Every layer is memory-bound at these widths,
so spreading the layers' sum evenly changes no least time.

The share is also taken for each kind of launch alone, gate+up and down
(a layer's launches run in that order), and both go to the note.  Where
either passes 100% the launches ran faster than the published peak lets
them, so the bytes, the clock or the peak is wrong there and the reader
gives nothing."""
from bench import program_stream, tracefile

KINDS = ("gate_up", "down")


def kinds(gmm, d, f):
    """Each launch's kind by its place in the round (gate+up, then down,
    per layer); None where an output's width says otherwise."""
    out = [KINDS[i % 2] for i in range(len(gmm))]
    if d != f and any(o.out[-1:] != ((f,) if k == "gate_up" else (d,))
                      for o, k in zip(gmm, out)):
        return None
    return out


def read(ctx):
    t, peak = ctx.trace, ctx.peak
    if t is None or peak is None:
        return None
    rec = program_stream.record(ctx)
    if rec is None:
        return None
    t0, t1 = t["t0"], t["t1"]
    n = sum(1 for name, a, _ in t["spans"]
            if name == "bench.round" and t0 <= a <= t1)
    rounds = rec["rounds"][:n]
    layers = program_stream.moe_layers(ctx.engine.target.cfg)
    gmm = tracefile.select(t["ops"], "^ragged_moe_ffn$", "^jit_round_fn$",
                           t0, t1)
    if (n == 0 or len(rounds) < n or len(gmm) != 2 * layers * n
            or any(r["experts_reached"] is None for r in rounds)):
        return None
    cfg = ctx.config["target"]
    kind = kinds(gmm, cfg["d_model"], cfg["moe_d_ff"])
    if kind is None:
        return None
    least = dict.fromkeys(KINDS, 0.0)
    for r in rounds:
        rows = (ctx.traffic["clients"] * (r["gamma"] + 1)
                * cfg["num_experts_per_tok"])
        calls = ctx.flops.gmm_calls(cfg, rows, r["experts_reached"] / layers)
        for k in KINDS:
            least[k] += layers * ctx.flops.least_time(
                calls[k]["flops"], calls[k]["bytes"], peak)
    device = dict.fromkeys(KINDS, 0.0)
    for o, k in zip(gmm, kind):
        device[k] += (min(o.end, t1) - o.start) * 1e-9
    if min(device.values()) <= 0:
        return None
    share = {k: 100.0 * least[k] / device[k] for k in KINDS}
    ctx.note(f"gmm roofline: {n} rounds, {len(gmm)} launches; " + "; ".join(
        f"{k} {device[k] * 1e3:.4f} ms on the device, least "
        f"{least[k] * 1e3:.4f} ms, {share[k]:.3f}%" for k in KINDS))
    if max(share.values()) > 100.0:
        return None
    return 100.0 * sum(least.values()) / sum(device.values())
