#!/usr/bin/env python3
"""Where a cell's host time goes, read from the program's own spans.

    python3 bench/spans.py --workload qwen2-57b-a14b.sd.chat-c16 \
        --seed 1234

Builds the cell's engine and serves its warm-up stream as a run of
``bench/run.py`` does, then one untraced stream whose records give the
round's dispatch time and the admission share
(``bench/program_stream.serve``), then one stream under the profiler over
the harness's own traced window (``harness.traced_stream``: from the
first round, ``harness.TRACE_SECONDS`` long).  From that window it prints
one JSON line:

  * ``spans``: each ``serve.*`` / ``sd.*`` host span that lies in the
    window: how many, and their mean and total ms;
  * ``idle_ms``: device idle time by the innermost program span that
    holds each gap's midpoint (``outside`` where none does);
  * ``gaps``: the longest idle gaps, so labelled: [span, ms, ms from
    the window's start]; ``first_op_ms``: when the window's first device
    op starts;
  * ``gmm``: the round's ragged-gmm launches by kind, in ms;
  * ``busy_s`` and ``window_s``, as ``device.idle_share`` reads them.

The harness's reader keeps only ``bench.*`` host spans; this one keeps
the program's too.  Needs a chip, as the benchmark does.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROGRAM = ("serve.", "sd.")


def breakdown(tr, d: int, f: int, n: int = 10) -> dict:
    """The window's spans, idle time and gmm launches (module doc)."""
    from bench import harness, tracefile
    t0, t1 = tr.span("bench.window")
    ops = tr.devices[sorted(tr.devices)[0]] if tr.devices else []
    prog = [s for s in tr.spans if s[0].startswith(PROGRAM)]
    spans = {}
    for name, a, b in prog:
        if t0 <= a and b <= t1:
            s = spans.setdefault(name, {"n": 0, "total_ms": 0.0})
            s["n"] += 1
            s["total_ms"] += (b - a) * 1e-6
    for s in spans.values():
        s["mean_ms"] = s["total_ms"] / s["n"]
    idle = tracefile.idle_by_activity(ops, prog, t0, t1)
    gaps = sorted(tracefile.idle_gaps(ops, t0, t1),
                  key=lambda g: g[0] - g[1])[:n]
    in_win = [o for o in ops if t0 <= o.start <= t1]
    gmm = tracefile.select(ops, "^ragged_moe_ffn$", "^jit_round_fn$", t0, t1)
    kind = harness.load_reader("kernel.moe_gmm_roofline").kinds(gmm, d, f)
    launches = {}
    for o, k in zip(gmm, kind or []):
        launches.setdefault(k, []).append((min(o.end, t1) - o.start) * 1e-6)
    return {
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": tracefile.busy_seconds(ops, t0, t1),
        "spans": dict(sorted(spans.items())),
        "idle_ms": {k: v * 1e3 for k, v in
                    sorted(idle.items(), key=lambda kv: -kv[1])},
        "gaps": [[tracefile.host_activity(prog, (a + b) / 2),
                  (b - a) * 1e-6, (a - t0) * 1e-6] for a, b in gaps],
        "first_op_ms": (min(o.start for o in in_win) - t0) * 1e-6
        if in_win else None,
        "gmm": {k: {"n": len(v), "mean_ms": sum(v) / len(v),
                    "min_ms": min(v), "max_ms": max(v)}
                for k, v in launches.items()},
    }


def measure(eng, reqs, config: dict, clients: int, root: str) -> dict:
    """One untraced stream's round split, then the traced window's
    breakdown, on a warm engine."""
    from bench import harness, program_stream, tracefile
    rec = program_stream.serve(eng, reqs, clients)
    rounds = rec["rounds"]
    layers = program_stream.moe_layers(eng.target.cfg)
    reached = [r["experts_reached"] for r in rounds]
    untraced = {
        "rounds": len(rounds),
        "dispatch_ms": 1e3 * sum(r["dispatch_time"] for r in rounds)
        / max(len(rounds), 1),
        "admit_share": 100.0 * rec["admit_s"] / rec["wall_s"],
        "experts_per_layer": (None if None in reached or not reached
                              else sum(reached) / len(reached) / layers),
    }
    keep = tracefile.SPAN_PREFIX
    tracefile.SPAN_PREFIX = ("bench.",) + PROGRAM
    try:
        _, tr = harness.traced_stream(eng, reqs, clients, root,
                                      harness.TRACE_SECONDS)
    finally:
        tracefile.SPAN_PREFIX = keep
    t = config["target"]
    return {"untraced": untraced,
            "traced": breakdown(tr, t["d_model"],
                                t.get("moe_d_ff", t["d_ff"]))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness, loadgen
    entry = harness.cell_entry(harness.load_benchmark(ROOT), args.workload)
    devs = harness.check_devices(entry["chips"], True)
    harness.enable_compile_cache(ROOT)
    config = harness.load_config(entry["config"])
    traffic = loadgen.load_traffic(entry["traffic"])
    reqs = loadgen.request_set(traffic, args.seed,
                               config["target"]["vocab_size"])
    eng = harness.build_engine(config, traffic, args.seed)
    harness.serve_stream(eng, reqs, traffic["clients"])
    out = measure(eng, reqs, config, traffic["clients"], ROOT)
    out.update(workload=args.workload, seed=args.seed,
               device=devs[0].device_kind)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
