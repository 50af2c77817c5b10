#!/usr/bin/env python3
"""Bring-up smoke test: the continuous speculative-decoding serving path,
end to end on a TPU, at the published widths of the paper's headline
target.

    python chip_smoke.py             # one chip: phases (a)-(e)
    python chip_smoke.py --chips 4   # four chips: expert-parallel serving
                                     # against the single-device run

Target: qwen2-57b-a14b at its published widths (d_model 3584, 28/4 heads,
64 experts top-8, 8x shared expert, vocab 151936) with depth cut from 28
to 2 layers, the only cut.  Draft: the paper's qwen2-0.5b at full size.
Weights are random, drawn from ``--seed``.

One chip runs, in order:

  (a) device     — JAX/libtpu versions and the device; anything but a TPU
                   is a failure (there is no CPU fallback);
  (b) kernels    — ``ragged_moe_ffn`` and ``paged_decode_attention`` at
                   this run's shapes, compiled (``interpret=False``),
                   against their ``ref.py`` oracles;
  (c) SD stream  — 16 requests, Poisson arrivals (admission mid-stream),
                   paged KV through the paged kernel, ragged-gmm MoE,
                   greedy, gamma 4 forced on;
  (d) AR stream  — the same requests with proposer "none", same weights;
  (e) warm replay of (c) under ``compile_guard`` and ``transfer_guard``.

``--chips 4`` runs only the expert-parallel path (``make_ep_mesh(4)``,
``moe_dispatch="ep"``, tp layout) and the single-device ``gmm`` run it is
compared with, on one of the four chips.

Tokens/s printed here are smoke wall clocks (first streams include
compilation), not benchmark metrics.  Any failure exits non-zero without
the result line; on success the last line of stdout is one JSON object
naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
import traceback
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: bf16 kernel parity: max|got - want| <= KERNEL_TOL * max|want|, with the
#: oracle in fp32 at "highest" matmul precision.  bf16 keeps 8 mantissa
#: bits (unit roundoff 2^-9); the kernels round their inputs and outputs
#: to bf16 once each, and the gmm rounds its hidden activations once more.
KERNEL_TOL = 2e-2
#: expert-parallel vs single-device verify step, gated on the norm-wise
#: relative error ||dlogits|| / ||logits|| <= EP_LOGIT_TOL.  The two runs
#: differ only in bf16 rounding order (tensor-parallel partial sums, fp32
#: vs bf16 expert combine), which moves logits by ~1% of their spread; but
#: routing is a discrete top-8 choice, so for the few tokens whose 8th and
#: 9th experts are near-tied the rounding flips an expert, and their
#: logits move by several percent (max|dlogit| 0.447 of max|logit| 6.44 on
#: the first four-chip run).  The norm bounds the bulk of the step; a
#: wrong shard combination (a missing expert slice, mismatched heads)
#: moves it by tens of percent.  max|dlogit| is printed, not gated.
EP_LOGIT_TOL = 5e-2


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or incomplete result."""


@dataclasses.dataclass(frozen=True)
class SmokeShape:
    """Model pair and traffic of one smoke run."""
    cfg: Any
    draft_cfg: Any
    requests: int = 16
    max_batch: int = 8
    max_new: int = 32
    gamma: int = 4
    page_size: int = 64
    arrival_rate: float = 0.5      # mean arrivals per decode round
    seed: int = 0
    reduced: str = ""              # what was cut from the published config


def published_shape(seed: int = 0) -> SmokeShape:
    from repro.configs.registry import get_config
    full = get_config("qwen2-57b-a14b")
    return SmokeShape(cfg=get_config("qwen2-57b-a14b", num_layers=2),
                      draft_cfg=get_config("qwen2-0.5b"), seed=seed,
                      reduced=f"num_layers {full.num_layers}->2")


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------- (a) device
def check_device(chips: int):
    import importlib.metadata

    import jax
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    devs = jax.devices()
    d = devs[0]
    log(f"(a) device: jax {jax.__version__}, libtpu {libtpu}, "
        f"platform={d.platform} kind={d.device_kind!r} count={len(devs)}")
    if d.platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX runs on {d.platform!r}")
    if len(devs) < chips:
        raise SmokeFailure(f"--chips {chips} needs {chips} devices, "
                           f"JAX sees {len(devs)}")
    return devs


def device_json() -> str:
    import jax
    devs = jax.devices()
    return json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}})


# ------------------------------------------------------------ (b) kernels
def _rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return float("inf")
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                   1e-30))


def kernel_parity(shape: SmokeShape, *, interpret: Optional[bool] = None):
    """The two serving kernels at this run's verify shapes against their
    oracles; raises :class:`SmokeFailure` on a miss."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.decode_attention.ops import paged_decode_attention
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    from repro.kernels.gmm.ragged import ragged_moe_ffn
    from repro.kernels.gmm.ref import ragged_moe_ffn_ref
    from repro.models.layers import dense_init

    cfg = shape.cfg
    dt = jnp.dtype(cfg.dtype)
    T = shape.gamma + 1
    keys = jax.random.split(jax.random.PRNGKey(shape.seed + 7), 8)

    # ragged expert FFN on one verify pass's routed rows
    E, K, d, f = (cfg.num_experts, cfg.num_experts_per_tok, cfg.d_model,
                  cfg.moe_d_ff)
    N = shape.max_batch * T * K
    xs = jax.random.normal(keys[0], (N, d), jnp.float32).astype(dt)
    wg = dense_init(keys[1], (E, d, f), dt)
    wu = dense_init(keys[2], (E, d, f), dt)
    wd = dense_init(keys[3], (E, f, d), dt)
    sizes = jnp.bincount(jax.random.randint(keys[4], (N,), 0, E), length=E)
    got = ragged_moe_ffn(xs, wg, wu, wd, sizes,
                         activation=cfg.mlp_activation, interpret=interpret)
    # the oracle gathers one (d, f) weight per row: run it on row chunks,
    # each with the group sizes of its slice of the expert-sorted rows
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    ref = jax.jit(ragged_moe_ffn_ref, static_argnames=("activation",))
    chunk = 32
    with jax.default_matmul_precision("highest"):
        want = jnp.concatenate([
            ref(xs[a:a + chunk], wg, wu, wd,
                jnp.clip(ends, a, a + chunk) - jnp.clip(starts, a, a + chunk),
                activation=cfg.mlp_activation)
            for a in range(0, N, chunk)])
    gmm_err = _rel_err(got, want)
    del xs, wg, wu, wd, got, want

    # paged decode/verify attention over a shuffled page pool
    B, Hq, Hkv, D, ps = (shape.max_batch, cfg.num_heads, cfg.num_kv_heads,
                         cfg.head_dim, shape.page_size)
    MP = 4
    NP = B * MP + 1
    table = (jax.random.permutation(keys[5], NP - 1) + 1).reshape(B, MP)
    lengths = jax.random.randint(keys[6], (B,), 0, MP * ps - T)
    kq, kk, kv = jax.random.split(keys[7], 3)
    q = jax.random.normal(kq, (B, T, Hq, D), jnp.float32).astype(dt)
    kp = jax.random.normal(kk, (NP, Hkv, ps, D), jnp.float32).astype(dt)
    vp = jax.random.normal(kv, (NP, Hkv, ps, D), jnp.float32).astype(dt)
    got = paged_decode_attention(q, kp, vp, lengths, table,
                                 interpret=interpret)
    with jax.default_matmul_precision("highest"):
        want = paged_decode_attention_ref(
            q.transpose(0, 2, 1, 3), kp, vp, lengths, table
        ).transpose(0, 2, 1, 3)
    attn_err = _rel_err(got, want)

    log(f"(b) kernels ({dt.name}, tolerance max|err| <= {KERNEL_TOL} "
        f"x max|ref|): ragged_moe_ffn N={N} d={d} F={f} E={E} "
        f"rel_err={gmm_err:.3e}; paged_decode_attention B={B} T={T} "
        f"Hq/Hkv={Hq}/{Hkv} D={D} page={ps} rel_err={attn_err:.3e}")
    for name, err in (("ragged_moe_ffn", gmm_err),
                      ("paged_decode_attention", attn_err)):
        if not err <= KERNEL_TOL:
            raise SmokeFailure(f"{name} parity miss: {err:.3e} > "
                               f"{KERNEL_TOL}")


# ------------------------------------------------------ (c)-(e) streaming
def engine_opts(shape: SmokeShape) -> Dict[str, Any]:
    return dict(max_batch=shape.max_batch, gamma=shape.gamma,
                temperature=0.0, scheduler="continuous", kv_layout="paged",
                page_size=shape.page_size)


def serve_stream(eng, shape: SmokeShape, label: str):
    """Submit the smoke's traffic, serve it, check every request finished
    cleanly; returns (report, outputs in submission order)."""
    import numpy as np
    from repro.data.pipeline import prompt_batch
    from repro.serving.scheduler import submit_poisson

    pb = prompt_batch(shape.cfg.vocab_size, shape.requests, kind="chat",
                      seed=shape.seed)
    uids = submit_poisson(eng, pb["tokens"], pb["lengths"],
                          rate=shape.arrival_rate,
                          max_new_choices=[shape.max_new], seed=shape.seed)
    reports = eng.run()
    rep = reports[-1]
    outs = [np.asarray(eng.done[u].output) for u in uids]
    reasons = {eng.done[u].finish_reason for u in uids}
    admitted_late = sum(1 for s in rep.steps[1:] if s.admitted)
    sd = (f"sigma={rep.stats.sigma:.4f} alpha={rep.stats.alpha:.4f}"
          if rep.used_sd else "AR")
    log(f"{label}: {rep.tokens_out} tokens in {rep.wall_time:.3f} s = "
        f"{rep.tokens_per_second:.2f} tok/s (smoke wall clock, not a "
        f"benchmark metric); {sd}; rounds={len(rep.steps)} "
        f"admission rounds after the first={admitted_late}; "
        f"finish_reasons={rep.finish_reasons}")
    bad = reasons - {"length", "eos"}
    if bad:
        raise SmokeFailure(f"{label}: requests finished {sorted(bad)} "
                           f"({rep.finish_reasons})")
    return rep, outs


def agreement(a: List, b: List) -> Dict[str, float]:
    """Greedy-token agreement between two runs of the same requests:
    the share of positions holding the same token, and the share of
    tokens inside each request's common prefix."""
    same = total = prefix = 0
    for x, y in zip(a, b):
        n = min(len(x), len(y))
        eq = [int(x[i]) == int(y[i]) for i in range(n)]
        same += sum(eq)
        total += max(len(x), len(y))
        prefix += next((i for i, e in enumerate(eq) if not e), n)
    return {"positions": same / max(total, 1),
            "prefix": prefix / max(total, 1)}


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} B ({peak / 1e9:.3f} GB)"


def run_one_chip(shape: SmokeShape, *, interpret: Optional[bool] = None):
    """Phases (b)-(e).  ``interpret`` steers the kernels of phase (b) only
    (tests run them on the CPU); the serving path decides per backend."""
    import jax
    from repro.analysis import compile_guard, transfer_guard
    from repro.launch.serve import build_engine
    from repro.serving.engine import ServingEngine

    kernel_parity(shape, interpret=interpret)
    gc.collect()

    t0 = time.perf_counter()
    opts = engine_opts(shape)
    sd_eng = build_engine(shape.cfg, proposer="model",
                          draft_cfg=shape.draft_cfg, moe_dispatch="gmm",
                          paged_attention="kernel", seed=shape.seed,
                          force_sd=True, **opts)
    jax.block_until_ready(sd_eng.params_t)
    log(f"engine: target {shape.cfg.param_count() / 1e9:.3f}B params, "
        f"draft {shape.draft_cfg.param_count() / 1e9:.3f}B params, "
        f"built in {time.perf_counter() - t0:.1f} s")

    _, sd_out = serve_stream(sd_eng, shape, "(c) SD stream")
    ar_eng = ServingEngine(sd_eng.target, None, sd_eng.params_t, None,
                           proposer="none", seed=shape.seed, **opts)
    _, ar_out = serve_stream(ar_eng, shape, "(d) AR stream")
    agree = agreement(sd_out, ar_out)
    log(f"SD vs AR greedy agreement: positions={agree['positions']:.4f} "
        f"common-prefix={agree['prefix']:.4f}")

    with compile_guard() as cg, transfer_guard() as tg:
        _, replay = serve_stream(sd_eng, shape, "(e) warm SD replay")
    same = all(len(a) == len(b) and (a == b).all()
               for a, b in zip(replay, sd_out))
    log(f"(e) warm replay: {cg.count} compile(s), {tg.count} implicit "
        f"transfer(s), tokens identical to (c): {same}")
    if not same:
        raise SmokeFailure("warm replay committed different tokens")
    log(f"peak_bytes_in_use: {peak_bytes(jax.devices()[0])}")


# ------------------------------------------------------------ four chips
def verify_step_logits(eng, shape: SmokeShape):
    """Logits of one verify step (gamma+1 tokens per row) through the
    engine's target: prefill the first ``max_batch`` prompts into a paged
    cache, then extend — the paged kernel and the engine's MoE dispatch."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.data.pipeline import prompt_batch
    from repro.models.model import PageAllocator

    model, B, ps = eng.target, shape.max_batch, shape.page_size
    pb = prompt_batch(shape.cfg.vocab_size, B, kind="chat", seed=shape.seed)
    toks = np.asarray(pb["tokens"], np.int32)
    max_seq = -(-(toks.shape[1] + shape.gamma + 1) // ps) * ps
    alloc = PageAllocator(B, ps, B * (max_seq // ps) + 1, max_seq // ps)
    for b in range(B):
        alloc.alloc(b, max_seq)
    cache = model.init_cache(B, max_seq, paged=True, page_size=ps,
                             pool_pages=alloc.pool_pages)
    cache["pages"]["table"] = jnp.asarray(alloc.table)
    _, cache = jax.jit(model.prefill)(eng.params_t, jnp.asarray(toks), cache,
                                      lengths=jnp.asarray(pb["lengths"]))
    verify = jnp.asarray(toks[:, :shape.gamma + 1])
    logits, _ = jax.jit(model.extend)(eng.params_t, verify, cache)
    return np.asarray(logits.astype(jnp.float32))


def run_four_chips(shape: SmokeShape, devices) -> None:
    """Expert-parallel serving on a 1x4 mesh vs the single-device gmm run
    on ``devices[0]``; raises :class:`SmokeFailure` past the tolerance."""
    import jax
    import numpy as np
    from repro.analysis import sharding_guard, transfer_guard
    from repro.launch.mesh import make_ep_mesh
    from repro.launch.serve import build_engine

    opts = engine_opts(shape)
    common = dict(proposer="model", draft_cfg=shape.draft_cfg,
                  paged_attention="kernel", seed=shape.seed, force_sd=True,
                  **opts)
    # the single-device reference runs first and is freed before the
    # sharded engine is built: both at once would not fit one chip
    with jax.default_device(devices[0]):
        ref = build_engine(shape.cfg, moe_dispatch="gmm", **common)
        _, ref_out = serve_stream(ref, shape, "single-device gmm stream")
        ref_logits = verify_step_logits(ref, shape)
    del ref
    gc.collect()

    mesh = make_ep_mesh(len(devices), devices=devices)
    ep = build_engine(shape.cfg, moe_dispatch="ep", mesh=mesh,
                      mesh_layout="tp", **common)
    w = ep.params_t["layers"][0]["ffn"]["w_gate"]
    placed = len(w.sharding.device_set)
    log(f"ep: mesh {dict(mesh.shape)}, expert weights {w.shape} on "
        f"{placed} device(s), spec {w.sharding.spec}; bytes in use per "
        f"device: {[(d.memory_stats() or {}).get('bytes_in_use') for d in devices]}")
    if placed != len(devices) or w.sharding.is_fully_replicated:
        raise SmokeFailure("expert weights are not split over the mesh")
    _, ep_out = serve_stream(ep, shape, f"ep={len(devices)} stream")
    ep_logits = verify_step_logits(ep, shape)
    with transfer_guard() as tg, sharding_guard(ep) as sg:
        _, replay = serve_stream(ep, shape, f"ep={len(devices)} warm replay")
    same = all(len(a) == len(b) and (a == b).all()
               for a, b in zip(replay, ep_out))
    log(f"ep warm replay: {tg.count} implicit transfer(s); {sg.render()}; "
        f"tokens identical: {same}")
    if not same:
        raise SmokeFailure("ep warm replay committed different tokens")

    agree = agreement(ref_out, ep_out)
    d = np.abs(ep_logits - ref_logits)
    scale = float(np.max(np.abs(ref_logits)))
    rel = float(np.linalg.norm(ep_logits - ref_logits)
                / np.linalg.norm(ref_logits))
    per_token = d.max(axis=-1).reshape(-1)           # (B * (gamma+1),)
    argmax_same = float(np.mean(ep_logits.argmax(-1)
                                == ref_logits.argmax(-1)))
    log(f"ep={len(devices)} vs one device: greedy agreement "
        f"positions={agree['positions']:.4f} "
        f"common-prefix={agree['prefix']:.4f}; verify step "
        f"({per_token.size} tokens): max|dlogit|={float(d.max()):.4e} "
        f"(max|logit|={scale:.4e}), per-token max|dlogit| median="
        f"{float(np.median(per_token)):.4e}, tokens above 5% of max|logit|="
        f"{int(np.sum(per_token > 0.05 * scale))}, argmax agreement="
        f"{argmax_same:.4f}; ||dlogit||/||logit||={rel:.4e} "
        f"(tolerance {EP_LOGIT_TOL})")
    if not np.isfinite(ep_logits).all():
        raise SmokeFailure("non-finite logits on the expert-parallel path")
    if not rel <= EP_LOGIT_TOL:
        raise SmokeFailure(f"ep verify logits differ by {rel:.4e} "
                           f"(norm-wise) > {EP_LOGIT_TOL}")
    if not sg.ok:
        raise SmokeFailure(sg.render())
    for d in devices:
        log(f"peak_bytes_in_use[{d.id}]: {peak_bytes(d)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the expert-parallel path and its "
                         "single-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        from repro.launch.compile_cache import enable_compile_cache
        devs = check_device(args.chips)
        log(f"compile cache: {enable_compile_cache()}")
        shape = published_shape(args.seed)
        log(f"reduced: {shape.reduced}")
        if args.chips == 4:
            run_four_chips(shape, devs[:4])
        else:
            run_one_chip(shape)
    except Exception:                 # any failure: report, exit non-zero
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(device_json(), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
