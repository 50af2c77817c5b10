#!/usr/bin/env python3
"""Bytes per second of the ragged gmm's launches beside a plain XLA read of
the same weights, on this machine's chip.

    python3 bench/gmm_probe.py [--config qwen2-57b-a14b.sd] [--launches 30]

At the configuration's widths (d_model, moe_d_ff, num_experts) and the
rows of its SD verify (16 clients x (gamma + 1) x top-k), for each count
of experts reached (``--experts``, rows spread evenly over them),
``ragged_moe_ffn`` runs ``--launches`` times back to back, twice: once
timed by the host clock (ended by ``block_until_ready``) and once under
the profiler, whose device events give each gate+up and down launch's
time.  Each time stands beside the bytes the launch must read
(``bench/flops.gmm_calls``).  A plain XLA reduction over one weight slab,
whose bytes are exact, gives the rate the chip's HBM reaches outside the
kernel.  A rate above the published peak (``bench/peaks.json``) on the
host clock is the chip's own; one above it on the device events alone is
the events'.  Prints one JSON line; needs a chip.
"""
import argparse
import glob
import json
import os
import shutil
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rates(ops, kinds, nbytes):
    """Per launch kind: device seconds (median, min, max) and GB/s."""
    out = {}
    for kind in sorted(set(kinds)):
        ts = [(o.end - o.start) * 1e-9 for o, k in zip(ops, kinds)
              if k == kind]
        med = statistics.median(ts)
        out[kind] = {"n": len(ts), "median_ms": med * 1e3,
                     "min_ms": min(ts) * 1e3, "max_ms": max(ts) * 1e3,
                     "bytes": nbytes[kind],
                     "gb_per_s_median": nbytes[kind] / med * 1e-9,
                     "gb_per_s_best": nbytes[kind] / min(ts) * 1e-9}
    return out


def _timed(jax, fn, args, n, tdir, module):
    """Host seconds per call over ``n`` calls, then the device ops of
    ``module`` over ``n`` more calls under the profiler."""
    from bench import tracefile
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    wall = (time.perf_counter() - t) / n
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(tdir)
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                        recursive=True)
    tr = tracefile.load(path)
    shutil.rmtree(tdir, ignore_errors=True)
    ops = [o for o in tr.devices[sorted(tr.devices)[0]]
           if o.module == module and o.name not in tracefile.CONTAINERS]
    return wall, ops


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="qwen2-57b-a14b.sd")
    ap.add_argument("--experts", type=int, nargs="+",
                    default=[64, 48, 32, 16])
    ap.add_argument("--launches", type=int, default=30)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import flops, harness
    from repro.kernels.gmm.ragged import ragged_moe_ffn

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("gmm_probe: no TPU", file=sys.stderr)
        return 1
    peak = flops.peaks(dev.device_kind)
    config = harness.load_config(args.config)
    cfg = config["target"]
    d, f, E = cfg["d_model"], cfg["moe_d_ff"], cfg["num_experts"]
    rows = 16 * (config["gamma"] + 1) * cfg["num_experts_per_tok"]
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    bf = jnp.bfloat16
    w_gate = (jax.random.normal(ks[0], (E, d, f), bf) * 0.02).astype(bf)
    w_up = (jax.random.normal(ks[1], (E, d, f), bf) * 0.02).astype(bf)
    w_down = (jax.random.normal(ks[2], (E, f, d), bf) * 0.02).astype(bf)
    xs = jax.random.normal(ks[3], (rows, d), bf)
    tdir = os.path.join(ROOT, ".bench_trace_probe")
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "peak_hbm_gb_per_s": peak["hbm_bytes_per_s"] * 1e-9,
              "rows": rows, "launches": args.launches, "gmm": {}}

    def hbm_read(w):
        return jnp.max(w, axis=0)
    wall, ops = _timed(jax, jax.jit(hbm_read), (w_gate,), args.launches,
                       tdir, "jit_hbm_read")
    slab = E * d * f * 2
    dev_s = sum(o.end - o.start for o in ops) * 1e-9 / args.launches
    result["xla_read"] = {"bytes": slab, "wall_ms": wall * 1e3,
                          "device_ms": dev_s * 1e3,
                          "gb_per_s_wall": slab / wall * 1e-9,
                          "gb_per_s_device": slab / dev_s * 1e-9}

    ffn = jax.jit(ragged_moe_ffn)
    for k in args.experts:
        sizes = np.zeros(E, np.int32)
        picked = np.linspace(0, E - 1, k).round().astype(int)
        sizes[picked] = rows // k
        sizes[picked[:rows % k]] += 1
        wall, ops = _timed(jax, ffn, (xs, w_gate, w_up, w_down,
                                      jnp.asarray(sizes)),
                           args.launches, tdir, "jit_ragged_moe_ffn")
        gmm = [o for o in ops if o.name == "ragged_moe_ffn"]
        kinds = ["gate_up" if o.out[-1:] == (f,) else "down" for o in gmm]
        calls = flops.gmm_calls(cfg, rows, k)
        nbytes = {kind: calls[kind]["bytes"] for kind in calls}
        least = sum(flops.least_time(c["flops"], c["bytes"], peak)
                    for c in calls.values())
        result["gmm"][str(k)] = {
            "wall_ms": wall * 1e3,
            "device_ms": sum(o.end - o.start for o in ops) * 1e-9
            / args.launches * 1e3,
            "least_ms": least * 1e3,
            "ops": sorted({o.name for o in ops}),
            "launches": _rates(gmm, kinds, nbytes) if gmm else {},
        }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
