#!/usr/bin/env python3
"""Sets of benchmark runs on this machine's chips, and their spreads.

    python3 bench/sets.py run --workload qwen2-57b-a14b.sd.chat-c16 \
        --label A --trace 0 --seconds 51 --seeds 11 12 13 \
        --out results/qwen2.jsonl
    python3 bench/sets.py summary results/qwen2.jsonl

``run`` starts ``bench/run.py`` once per seed, one after another (this
process never touches JAX, so each run holds the chip alone), and appends
one JSON line per run to ``--out``: label, seed, exit code, wall seconds,
and the run's result line (its standard error goes beside it, in
``<out>.<label>.<seed>.err``).  It stops at the first run that prints no
result.  ``summary`` prints, per workload, label and trace flag, each
metric's median and spread (interquartile range over the median, from
``statistics.quantiles(values, n=4)``) and each compared number.
"""
import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def last_json(text: str):
    for line in reversed(text.strip().splitlines()):
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "correct" in obj:
            return obj
    return None


def run(args) -> int:
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    for seed in args.seeds:
        cmd = [sys.executable, os.path.join(ROOT, "bench", "run.py"),
               "--workload", args.workload, "--seed", str(seed),
               "--seconds", str(args.seconds), "--trace", str(args.trace)]
        t = time.perf_counter()
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                           timeout=args.timeout)
        wall = time.perf_counter() - t
        with open(f"{args.out}.{args.label}.{seed}.err", "w") as f:
            f.write(p.stderr)
        res = last_json(p.stdout)
        row = {"workload": args.workload, "label": args.label,
               "trace": args.trace, "seed": seed, "rc": p.returncode,
               "wall_s": wall, "result": res}
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
        print(json.dumps(row), flush=True)
        if res is None:
            print(p.stderr[-3000:], file=sys.stderr, flush=True)
            return 1
    return 0


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def summary(path: str) -> int:
    rows = [json.loads(line) for line in open(path) if line.strip()]
    groups = {}
    for r in rows:
        groups.setdefault((r["workload"], r["label"], r["trace"]), []).append(r)
    for (w, label, trace), rs in sorted(groups.items()):
        ok = [r["result"] for r in rs if r["result"]]
        print(f"== {w} set {label} trace {trace}: {len(rs)} runs, seeds "
              f"{[r['seed'] for r in rs]}, correct "
              f"{[x['correct'] for x in ok]}, wall "
              f"{[round(r['wall_s'], 1) for r in rs]}")
        for m in sorted({m for x in ok for m in x["metrics"]}):
            v = [x["metrics"][m]["value"] for x in ok if m in x["metrics"]]
            sp = spread(v) if len(v) >= 2 else float("nan")
            print(f"   {m:32s} median {statistics.median(v):14.6f} spread "
                  f"{100 * sp:8.4f}%  {v}")
        for c in sorted({c for x in ok for c in x.get("checks", {})}):
            print(f"   check {c}: {[x['checks'][c]['value'] for x in ok]}")
        print(f"   memory_peak_bytes "
              f"{[x['device'].get('memory_peak_bytes') for x in ok]}")
        for x in ok:
            if "busy_s" in x["device"]:
                print(f"   busy_s {x['device']['busy_s']} window_s "
                      f"{x['device']['window_s']}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    r = sub.add_parser("run")
    r.add_argument("--workload", required=True)
    r.add_argument("--label", required=True)
    r.add_argument("--trace", type=int, choices=(0, 1), default=0)
    r.add_argument("--seconds", type=float, required=True)
    r.add_argument("--seeds", type=int, nargs="+", required=True)
    r.add_argument("--out", required=True)
    r.add_argument("--timeout", type=float, default=1500)
    s = sub.add_parser("summary")
    s.add_argument("path")
    args = ap.parse_args(argv)
    return run(args) if args.cmd == "run" else summary(args.path)


if __name__ == "__main__":
    sys.exit(main())
