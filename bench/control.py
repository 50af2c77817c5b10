#!/usr/bin/env python3
"""Readings that set a cell's ``mean_logit_gap`` limit, taken on the chip.

    python3 bench/control.py --workload qwen2-57b-a14b.sd.chat-c16 \
        --seeds 11 12 13 --quant fp8 int8

For each seed, in one process: the cell's engine is built and serves its
request set once (the cell's own load, every request to its end), its
state is freed, and over the same sample of finished requests that a run
compares, the reference reads

  * ``served``: the gaps of the program's served tokens (the lower
    reading, over a dozen seeds or more with the benchmark's own runs);
  * ``control.<quant>``: the gaps of the tokens the reference computed in
    ``quant`` puts first (the upper reading);

each summarised by ``check.summary`` (mean, widest, 99th percentile,
share of tokens off the reference's best).

One JSON line per seed.  The benchmark's runs never run this.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(cell: str, seed: int, quants, *, config=None, traffic=None,
             require_tpu: bool = True) -> dict:
    from bench import check, harness, loadgen
    bench = harness.load_benchmark()
    entry = harness.cell_entry(bench, cell)
    harness.check_devices(entry["chips"], require_tpu)
    config = config or harness.load_config(entry["config"])
    traffic = traffic or loadgen.load_traffic(entry["traffic"])
    reqs = loadgen.request_set(traffic, seed, config["target"]["vocab_size"])
    eng = harness.build_engine(config, traffic, seed)
    rec = harness.serve_stream(eng, reqs, traffic["clients"])
    del eng
    gc.collect()
    finished = [r for r in rec["requests"] if r["reason"] in harness.CLEAN]
    picked = check.sample(finished, seed, config["check"]["sample"])
    length = loadgen.longest_sequence(traffic)
    ref = check.load_reference(config["reference"])
    g = check.gaps(ref, config["target"], seed, picked, length, quants)
    return {"seed": seed, "finished": len(finished),
            "attempted": len(rec["requests"]),
            "served": check.summary(g["served"]),
            "control": {q: check.summary(g[q]) for q in quants}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--quant", nargs="*", default=["fp8", "int8"])
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    harness.enable_compile_cache(ROOT)
    for s in args.seeds:
        t = time.perf_counter()
        r = readings(args.workload, s, args.quant)
        r["seconds"] = time.perf_counter() - t
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
