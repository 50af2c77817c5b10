#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload qwen2-57b-a14b.sd.chat-c16 \
        --seed 1234 --seconds 30 --trace 0

Cells, metrics and bounds are in ``BENCHMARK.json``; ``bench/harness.py``
says what a run does.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and
``checks``: each number compared beside its limit, which are also the last
lines of standard error.  Without an accelerator, or with fewer chips than
the cell asks for, or outside a checkout of the repository, it exits
non-zero and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    try:
        from bench import harness
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), root=ROOT, t0=T0)
    except Exception:
        traceback.print_exc()
        print("bench: FAILED, no result", file=sys.stderr, flush=True)
        return 1
    for line in harness.check_lines(result):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
