"""One run of one benchmark cell: set-up, warm-up, a measured window of
closed-loop streams, the cell's metrics, and the comparison with the
reference that decides ``correct``.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``bench/configs/<config>.json`` (target sizes,
proposer, serving options, reference, limits), its traffic in
``bench/traffic/<traffic>.json`` (read by ``loadgen.py``) and each metric's
reader in ``bench/metrics/<metric>.py`` (``read(ctx)`` returns a number,
or ``None`` when it finds nothing to read).

Timeline of a run, all on the one process that holds the chip:

  set-up   device check, compile cache, weights drawn on the device from
           the seed, engine built, the request set served once (this
           compiles or loads every program the window runs);
  window   the same request set served again and again, whole streams,
           until ``seconds`` have passed; compiles inside it are counted;
  traced   (``trace=1`` only) one more stream, under the profiler from its
           first round for ``TRACE_SECONDS``, then the readers that need
           the live engine;
  check    program state freed, then the reference over a sample of the
           window's finished requests.
"""
from __future__ import annotations

import gc
import glob
import importlib.util
import json
import os
import shutil
import sys
import threading
import time
from types import SimpleNamespace
from typing import List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

from bench import check, flops, loadgen, tracefile, weights  # noqa: E402


class NoDevice(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


# ------------------------------------------------------------- lookups
def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def cell_entry(bench: dict, name: str) -> dict:
    for c in bench["workloads"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The cell's end-to-end metrics (``trace`` false) or per-layer ones."""
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def load_reader(metric: str):
    path = os.path.join(HERE, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------- runtime
def enable_compile_cache(root: str) -> str:
    """JAX's persistent cache at a fixed directory inside the checkout,
    every program cached, so only a checkout's first run compiles."""
    import jax
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = [0]
_compiles_lock = threading.Lock()


def _on_event(event: str, duration: float, **kw) -> None:
    if event == _COMPILE_EVENT:
        with _compiles_lock:
            _compiles[0] += 1


def install_compile_counter() -> None:
    """Counts XLA backend compilations (a persistent-cache hit is not
    one); installed once per process."""
    if not getattr(install_compile_counter, "done", False):
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_event)
        install_compile_counter.done = True


def compile_count() -> int:
    with _compiles_lock:
        return _compiles[0]


def check_devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"no accelerator: JAX runs on {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chip(s), JAX sees "
                       f"{len(devs)}")
    return devs


def model_config(d: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(**{k: tuple(v) if isinstance(v, list) else v
                          for k, v in d.items()})


def build_engine(config: dict, traffic: dict, seed: int):
    """The served system, as the configuration states it, with target
    weights drawn by ``bench/weights.py`` and draft weights by the
    program's own initializer (both on the device, one call each)."""
    import jax
    from repro.models.model import Model
    from repro.serving.engine import ServingEngine
    srv = config["serving"]
    target = Model(model_config(config["target"]),
                   moe_dispatch=srv["moe_dispatch"],
                   paged_attention=srv["paged_attention"])
    params_t = weights.target_params(config["target"], seed)
    if not weights.same_layout(params_t, jax.eval_shape(
            target.init, jax.random.PRNGKey(0))):
        raise RuntimeError("drawn target weights do not match the model's "
                           "parameter layout")
    kind = config["proposer"]
    draft, params_d = None, None
    if kind == "eagle":
        from repro.core.eagle import EagleHead
        draft = EagleHead(target)
    elif kind == "model":
        draft = Model(model_config(config["draft"]))
    if draft is not None:
        params_d = jax.jit(draft.init)(
            jax.random.fold_in(weights.seed_key(seed), 1))
    eng = ServingEngine(
        target, draft, params_t, params_d, proposer=kind,
        max_batch=traffic["clients"], gamma=config["gamma"],
        temperature=srv["temperature"], force_sd=config.get("force_sd"),
        scheduler=srv["scheduler"], kv_layout=srv["kv_layout"],
        page_size=srv["page_size"], seed=seed % (2 ** 31))
    jax.block_until_ready((params_t, params_d))
    return eng


# ------------------------------------------------------------- serving
def serve_stream(eng, reqs: List[loadgen.RequestSpec], clients: int) -> dict:
    """Serve one closed-loop stream of ``reqs`` to completion; the
    record of every request and round."""
    t_start = time.perf_counter()
    uids = [eng.submit(r.prompt, r.max_new) for r in reqs]
    reports = eng.run()
    t_end = time.perf_counter()
    done = [eng.done.pop(u) for u in uids]
    eng.reports.clear()
    sends = loadgen.send_times(t_start, [r.finished_at for r in done],
                               clients)
    steps = [s for rep in reports for s in (rep.steps or [])]
    return {
        "t_start": t_start, "wall_s": t_end - t_start,
        "report_wall_s": sum(rep.wall_time for rep in reports),
        "round_times": [s.round_time for s in steps if s.live > 0],
        "requests": [{"prompt": spec.prompt,
                      "output": np.asarray(r.output if r.output is not None
                                           else [], np.int32),
                      "reason": r.finish_reason,
                      "finished_at": r.finished_at, "sent_at": s}
                     for spec, r, s in zip(reqs, done, sends)],
    }


CLEAN = ("length", "eos")
#: how much of the traced stream the profiler records
TRACE_SECONDS = 5.0


def _annotate_session(eng, before_round, after_round):
    """Host spans around the session calls the scheduler makes, for the
    traced stream; ``before_round`` and ``after_round`` are called around
    each round.  Returns an undo function."""
    import jax
    sess = eng._session(eng.proposer_kind)
    names = ("round", "admit_rows", "grow_session", "start")

    def wrap(fn, label):
        def inner(*a, **k):
            if label == "bench.round":
                before_round()
            with jax.profiler.TraceAnnotation(label):
                out = fn(*a, **k)
            if label == "bench.round":
                after_round()
            return out
        return inner

    for n in names:
        setattr(sess, n, wrap(getattr(sess, n), "bench." + n))

    def undo():
        for n in names:
            delattr(sess, n)
    return undo


def traced_stream(eng, reqs, clients: int, root: str, seconds: float):
    """One more stream, under the profiler from its first round (after the
    stream's opening admission of every slot) for ``seconds`` (a whole
    stream's trace runs to millions of ops); its record and the reduced
    trace, whose window is the host span ``bench.window``."""
    import jax
    tdir = os.path.join(root, ".bench_trace")
    shutil.rmtree(tdir, ignore_errors=True)
    state = {"t0": 0.0, "on": False, "done": False, "window": None}

    def start():
        if not state["on"] and not state["done"]:
            jax.profiler.start_trace(tdir)
            # made after start_trace: an annotation made before records
            # nothing
            state["window"] = jax.profiler.TraceAnnotation("bench.window")
            state["window"].__enter__()
            state.update(t0=time.perf_counter(), on=True)

    def stop(force=False):
        if state["on"] and (force or
                            time.perf_counter() - state["t0"] >= seconds):
            state["window"].__exit__(None, None, None)
            jax.profiler.stop_trace()
            state.update(on=False, done=True)

    undo = _annotate_session(eng, start, stop)
    try:
        rec = serve_stream(eng, reqs, clients)
    finally:
        stop(force=True)
        undo()
    files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    t = time.perf_counter()
    tr = tracefile.load(files[0]) if files else None
    size = os.path.getsize(files[0]) if files else 0
    shutil.rmtree(tdir, ignore_errors=True)
    ops = sum(len(v) for v in tr.devices.values()) if tr else 0
    print(f"trace: {size} bytes, {ops} device ops, read in "
          f"{time.perf_counter() - t:.3f} s", file=sys.stderr, flush=True)
    return rec, tr


def device_info(devs, chips: int) -> dict:
    used = devs[:chips]
    peaks = [((d.memory_stats() or {}).get("peak_bytes_in_use")) for d in used]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(peaks) if peaks else None}


def trace_summary(tr, chips: int) -> Optional[dict]:
    """busy/window seconds (busy averaged over the chips used), the first
    chip's ops, and the host spans."""
    if tr is None or not tr.devices:
        return None
    win = tr.span("bench.window")
    if win is None:
        return None
    t0, t1 = win
    devs = sorted(tr.devices)[:chips]
    busy = [tracefile.busy_seconds(tr.devices[d], t0, t1) for d in devs]
    ops = tr.devices[devs[0]]
    return {"t0": t0, "t1": t1, "window_s": (t1 - t0) * 1e-9,
            "busy_s": sum(busy) / len(busy), "ops": ops, "spans": tr.spans}


# ------------------------------------------------------------- the run
def run(cell: str, seed: int, seconds: float, trace: bool, *,
        root: str = ROOT, t0: Optional[float] = None,
        require_tpu: bool = True, config_override: Optional[dict] = None,
        traffic_override: Optional[dict] = None) -> dict:
    """One run of ``cell``; returns the result object (see run.py).

    ``config_override`` / ``traffic_override`` replace the configuration
    and traffic files (tests run a cell at a CPU size with them);
    ``require_tpu=False`` skips the look for a chip."""
    t0 = time.perf_counter() if t0 is None else t0
    def say(msg):
        print(msg, file=sys.stderr, flush=True)
    bench = load_benchmark(root)
    entry = cell_entry(bench, cell)
    devs = check_devices(entry["chips"], require_tpu)
    import jax
    if require_tpu:
        say(f"compile cache: {enable_compile_cache(root)}")
    install_compile_counter()
    config = config_override or load_config(entry["config"])
    traffic = traffic_override or loadgen.load_traffic(entry["traffic"])
    vocab = config["target"]["vocab_size"]
    reqs = loadgen.request_set(traffic, seed, vocab)
    clients = traffic["clients"]

    eng = build_engine(config, traffic, seed)
    t_b = time.perf_counter()
    warm = serve_stream(eng, reqs, clients)
    setup_s = time.perf_counter() - t0
    say(f"set-up {setup_s:.3f} s (engine built at {t_b - t0:.3f} s, "
        f"warm-up stream {warm['wall_s']:.3f} s, {compile_count()} "
        f"compiles)")

    c0 = compile_count()
    streams, tw0 = [], time.perf_counter()
    while not streams or time.perf_counter() - tw0 < seconds:
        streams.append(serve_stream(eng, reqs, clients))
    window_s = time.perf_counter() - tw0
    in_window = compile_count() - c0
    print(f"compiles in window: {in_window}", flush=True)
    say(f"window {window_s:.3f} s, {len(streams)} stream(s), "
        f"{in_window} compile(s)")

    peak = flops.peaks(devs[0].device_kind) if require_tpu else None
    ctx = SimpleNamespace(
        cell=entry, config=config, traffic=traffic, seed=seed,
        seconds=seconds, streams=streams, window_s=window_s,
        setup_s=setup_s, compiles_in_window=in_window, peak=peak,
        chips=entry["chips"], engine=eng, trace=None, note=say, flops=flops)
    breakdown = None
    if trace:
        _, tr = traced_stream(eng, reqs, clients, root, TRACE_SECONDS)
        ctx.trace = trace_summary(tr, entry["chips"])
        if ctx.trace is not None:
            s = ctx.trace
            breakdown = {
                "device_ops": tracefile.top_ops(s["ops"], s["t0"], s["t1"]),
                "idle_gaps": tracefile.longest_gaps(s["ops"], s["spans"],
                                                    s["t0"], s["t1"])}
            idle = tracefile.idle_by_activity(s["ops"], s["spans"], s["t0"],
                                              s["t1"])
            say("idle seconds by host activity: " + json.dumps(idle))
    metrics = {}
    for m in cell_metrics(bench, cell, trace):
        v = load_reader(m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = device_info(devs, entry["chips"])
    if trace and ctx.trace is not None:
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]

    finished, attempted, failed = [], 0, 0
    for st in streams:
        for r in st["requests"]:
            attempted += 1
            if r["reason"] in CLEAN:
                finished.append(r)
            else:
                failed += 1
    del ctx, eng
    gc.collect()

    # ---- the comparison, with the program's state freed
    chk = config["check"]
    ref = check.load_reference(config["reference"])
    picked = check.sample(finished, seed, chk["sample"])
    length = loadgen.longest_sequence(traffic)
    t_c = time.perf_counter()
    g = check.gaps(ref, config["target"], seed, picked, length)["served"]
    st = check.summary(g)
    say(f"reference over {len(picked)} requests, {st['n']} served tokens in "
        f"{time.perf_counter() - t_c:.3f} s; gaps {json.dumps(st)}")
    mean = st.get("mean", float("inf"))
    checks = {
        "mean_logit_gap": {"value": mean, "limit": chk["mean_logit_gap"]},
        "failed_requests": {"value": failed, "limit": 0},
    }
    correct = mean <= chk["mean_logit_gap"] and failed == 0
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def check_lines(result: dict) -> List[str]:
    return [f"check {k}: {v['value']} (limit {v['limit']})"
            for k, v in result["checks"].items()]
