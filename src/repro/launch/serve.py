"""Serving launcher: batched speculative decoding with auto-tuned gamma.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-57b-a14b --reduced \
      --requests 16 --max-batch 8 --max-new 32 --proposer model

``--proposer`` selects the drafting strategy through the Proposer registry
(core/proposer.py): "model" (small draft model), "eagle" (speculation head
on the target's features), "prefetch" (small draft model + draft-phase
expert warming, printing per-wave hit rates — core/prefetch.py), or "none"
(plain AR baseline).

``--scheduler continuous`` switches from wave decoding to the slot
scheduler (serving/scheduler.py): a fixed pool of KV slots, per-slot
retirement, in-flight admission between rounds and {use_sd, gamma}
re-planned on the live slot count every round.  ``--arrival-rate`` replays
a Poisson arrival trace (mean arrivals per decode round) and
``--mixed-max-new`` draws each request's budget from a comma list — the
mixed-length traffic where wave padding costs the most.

Admission knobs (continuous mode): ``--admit-mode sliced`` (default)
prefills only the admitted rows per refill (``full`` keeps the legacy
pool-wide prefill for comparison); ``--prefill-chunk N`` prefills long
prompts N tokens per round boundary instead of stalling one round;
``--kv-layout paged --page-size N`` stores target KV in block-table pages
so capacity grows with the traffic instead of being sized for the
worst-case request up front.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs.registry import draft_for, get_config
from repro.core.analytics import occupancy_timeline
from repro.core.autotune import AutoTuner
from repro.core.proposer import registered_proposers
from repro.data.pipeline import prompt_batch
from repro.data.tokenizer import ByteTokenizer
from repro.distributed.sharding import shard_params
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import Model
from repro.serving.engine import ServingEngine
from repro.serving.scheduler import submit_poisson


def init_params(model, key, *, mesh=None, mesh_layout: str = "tp"):
    """``model.init(key)`` placed for serving.  With a mesh the init runs
    as one program whose outputs land sharded (``shard_params``), so no
    device ever holds the whole model on the way."""
    if mesh is None:
        return model.init(key)
    shapes = jax.eval_shape(model.init, key)
    return jax.jit(model.init, out_shardings=shard_params(
        shapes, mesh, layout=mesh_layout))(key)


def build_engine(cfg, *, proposer: str = "model", draft_cfg=None,
                 moe_dispatch: str = "gmm", paged_attention: str = "kernel",
                 mesh=None, mesh_layout: str = "tp", seed: int = 0,
                 **engine_opts) -> ServingEngine:
    """Target (and draft) with weights drawn from ``seed``, behind one
    :class:`ServingEngine` — the construction the CLI and ``chip_smoke.py``
    share.  ``draft_cfg`` is the draft model for the "model"/"prefetch"
    proposers; "eagle" builds a head on the target, "none" serves AR.
    ``engine_opts`` go to ``ServingEngine`` unchanged."""
    layout = mesh_layout if mesh is not None else None
    target = Model(cfg, moe_dispatch=moe_dispatch,
                   paged_attention=paged_attention, mesh=mesh,
                   mesh_layout=layout)
    params_t = init_params(target, jax.random.PRNGKey(seed), mesh=mesh,
                           mesh_layout=mesh_layout)
    if proposer == "eagle":
        from repro.core.eagle import EagleHead
        draft = EagleHead(target)
    elif proposer == "none":
        draft = None
    else:
        draft = Model(draft_cfg)
    params_d = None if draft is None else init_params(
        draft, jax.random.PRNGKey(seed + 1), mesh=mesh,
        mesh_layout=mesh_layout)
    return ServingEngine(target, draft, params_t, params_d,
                         proposer=proposer, seed=seed, mesh=mesh,
                         mesh_layout=layout, **engine_opts)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--gamma", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--kind", default="chat", choices=["code", "chat"])
    ap.add_argument("--proposer", default="model",
                    choices=sorted(registered_proposers()),
                    help="drafting strategy (Proposer registry kind)")
    ap.add_argument("--prefetch-top-m", type=int, default=None,
                    help="experts to warm per period-slot with --proposer "
                         "prefetch (default: min(E, 2K))")
    ap.add_argument("--moe-dispatch", default="gmm",
                    choices=["onehot", "gmm", "ep"],
                    help="MoE dispatch for the decode path; the serving "
                         "default is the ragged grouped-matmul kernel "
                         "(training keeps onehot); ep = mesh-sharded "
                         "experts with all-to-all dispatch (--ep-degree)")
    ap.add_argument("--ep-degree", type=int, default=1,
                    help="expert-parallel shards: builds a (1, N) "
                         "('data','model') mesh, shards expert weights "
                         "over it and serves through the all-to-all "
                         "ragged dispatch (forces --moe-dispatch ep when "
                         "> 1; docs/distributed.md)")
    ap.add_argument("--mesh-layout", default="tp", choices=["tp", "fsdp"],
                    help="parameter layout on the mesh for the non-expert "
                         "weights (distributed/sharding.param_spec)")
    ap.add_argument("--scheduler", default="wave",
                    choices=["wave", "continuous"],
                    help="wave: static batch per wave; continuous: slot "
                         "pool with in-flight admission and per-round "
                         "N(t) re-planning (serving/scheduler.py)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="continuous mode: Poisson mean arrivals per decode "
                         "round (0 = everything arrives at round 0)")
    ap.add_argument("--mixed-max-new", default=None,
                    help="comma list of max_new_tokens choices drawn per "
                         "request (default: --max-new for every request)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="early-exit token id (per-request finish_reason)")
    ap.add_argument("--admit-mode", default="sliced",
                    choices=["sliced", "full"],
                    help="continuous admission: prefill only the admitted "
                         "rows (sliced, default) or the whole pool (full, "
                         "the legacy path kept for comparison)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="continuous mode: prefill prompts longer than "
                         "this in chunks interleaved with decode rounds")
    ap.add_argument("--kv-layout", default="dense",
                    choices=["dense", "paged"],
                    help="target KV layout; paged = block-table pages "
                         "with on-demand growth (continuous mode)")
    ap.add_argument("--page-size", type=int, default=64,
                    help="positions per KV page with --kv-layout paged")
    ap.add_argument("--paged-attention", default="kernel",
                    choices=["kernel", "gather"],
                    help="paged decode/verify attention: the block-table-"
                         "walking Pallas kernel (default) or the dense "
                         "pool[table] gather fallback "
                         "(docs/paged_attention.md)")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="paged mode: admissions whose prompt shares a "
                         "page-aligned prefix with a live slot fork its "
                         "pages (refcounted CoW) and prefill only the tail")
    ap.add_argument("--admission-order", default="fifo",
                    choices=["fifo", "pressure"],
                    help="continuous refill order; pressure picks the "
                         "smallest-page-footprint admissible request when "
                         "the paged pool is under pressure")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many common system-prompt tokens to "
                         "every request (exercises --prefix-sharing)")
    ap.add_argument("--round-deadline-s", type=float, default=None,
                    help="resilience: per-round wall-clock deadline; "
                         "slower rounds count toward the degradation "
                         "ladder (docs/faults.md)")
    ap.add_argument("--max-rounds-per-request", type=int, default=None,
                    help="resilience: per-request round budget "
                         "(finish_reason='timeout' past it)")
    ap.add_argument("--free-page-watermark", type=float, default=0.0,
                    help="resilience: defer admissions that would leave "
                         "the paged pool's free fraction below this")
    ap.add_argument("--max-pool-pages", type=int, default=None,
                    help="resilience: hard cap on paged pool growth; at "
                         "the cap page pressure preempts the youngest "
                         "slot (vLLM-style recompute requeue)")
    ap.add_argument("--transfer-guard", action="store_true",
                    help="after the stream completes, replay the same "
                         "workload through the warm engine under "
                         "transfer_guard + sharding_guard and fail on any "
                         "implicit host transfer or second input-sharding "
                         "signature (docs/analysis.md)")
    ap.add_argument("--timed", action="store_true",
                    help="record per-phase propose/verify/reject timings")
    ap.add_argument("--no-autotune", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    mesh = None
    if args.ep_degree > 1:
        from repro.launch.mesh import make_ep_mesh
        if args.moe_dispatch != "ep":
            print(f"--ep-degree {args.ep_degree}: forcing --moe-dispatch ep")
            args.moe_dispatch = "ep"
        if cfg.num_experts % args.ep_degree != 0:
            raise SystemExit(
                f"--ep-degree {args.ep_degree} does not divide "
                f"num_experts={cfg.num_experts} for {args.arch}")
        mesh = make_ep_mesh(args.ep_degree)
        print(f"mesh: {dict(mesh.shape)} layout={args.mesh_layout} "
              f"({len(mesh.devices.flat)} devices)")
    if args.proposer in ("none", "eagle"):
        dcfg = None
    elif args.reduced:
        dcfg = draft_for(cfg).with_overrides(
            num_layers=2, d_model=128, num_heads=4, num_kv_heads=2,
            d_ff=256, dtype="float32")
    else:
        dcfg = draft_for(cfg)

    if args.no_autotune or args.proposer == "none":
        tuner = None
    else:
        full_cfg = get_config(args.arch)
        if args.proposer == "eagle":
            # price the drafter as the head actually serving (one block on
            # the full target), not a standalone small model
            from repro.core.eagle import EagleHead
            tuner_draft = EagleHead(Model(full_cfg)).cfg
        else:
            tuner_draft = draft_for(full_cfg)
        tuner = AutoTuner(full_cfg, tuner_draft, alpha=0.7)
    proposer_opts = {}
    if args.proposer == "prefetch" and args.prefetch_top_m is not None:
        proposer_opts["top_m"] = args.prefetch_top_m
    from repro.serving.faults import ResilienceConfig
    resilience = ResilienceConfig(
        round_deadline_s=args.round_deadline_s,
        max_rounds_per_request=args.max_rounds_per_request,
        free_page_watermark=args.free_page_watermark,
        max_pool_pages=args.max_pool_pages)
    eng = build_engine(cfg, proposer=args.proposer, draft_cfg=dcfg,
                       moe_dispatch=args.moe_dispatch,
                       paged_attention=args.paged_attention, mesh=mesh,
                       mesh_layout=args.mesh_layout, seed=args.seed,
                       max_batch=args.max_batch, tuner=tuner,
                       gamma=args.gamma, temperature=args.temperature,
                       proposer_opts=proposer_opts, timed=args.timed,
                       scheduler=args.scheduler, eos_id=args.eos_id,
                       admit_mode=args.admit_mode,
                       prefill_chunk=args.prefill_chunk,
                       kv_layout=args.kv_layout, page_size=args.page_size,
                       prefix_sharing=args.prefix_sharing,
                       admission_order=args.admission_order,
                       resilience=resilience)

    pb = prompt_batch(cfg.vocab_size, args.requests, kind=args.kind,
                      seed=args.seed)
    if args.shared_prefix > 0:
        # one common system prompt ahead of every request — the workload
        # shape prefix sharing is built for
        rng = np.random.default_rng(args.seed + 17)
        sys_toks = rng.integers(1, cfg.vocab_size,
                                size=args.shared_prefix).astype(np.int32)
        pb["tokens"] = [np.concatenate([sys_toks, np.asarray(
            pb["tokens"][i][: int(pb["lengths"][i])], np.int32)])
            for i in range(len(pb["lengths"]))]
        pb["lengths"] = [int(n) + args.shared_prefix
                         for n in pb["lengths"]]
    max_new_choices = ([int(x) for x in args.mixed_max_new.split(",")]
                       if args.mixed_max_new else [args.max_new])
    submit_poisson(eng, pb["tokens"], pb["lengths"],
                   rate=args.arrival_rate, max_new_choices=max_new_choices,
                   seed=args.seed)

    reports = eng.run()
    tok = ByteTokenizer(cfg.vocab_size)
    for r in reports:
        # AR waves carry SDStats too (same loop) but sigma/alpha are
        # degenerate there — label them as the baseline
        sd = (f"sigma={r.stats.sigma:.3f} alpha={r.stats.alpha:.3f} "
              f"rounds={r.stats.rounds}" if r.used_sd and r.stats else "AR")
        timing = (f" propose={r.propose_time:.3f}s verify={r.verify_time:.3f}s"
                  f" reject={r.reject_time:.3f}s" if args.timed else "")
        if args.timed and r.warm_time:
            timing += f" warm={r.warm_time:.3f}s"
        # gate on the stats, not the kind string: any provides_prefetch
        # proposer populates the accounting
        pf = (f" prefetch_hit={r.prefetch_hit_rate:.2f} "
              f"({r.prefetch_hits}/{r.stats.prefetch_actual})"
              if r.stats and r.stats.prefetch_actual else "")
        print(f"{r.scheduler}: B={r.batch}/{r.bucket} gamma={r.gamma} "
              f"proposer={r.proposer} dispatch={r.moe_dispatch} "
              f"sd={r.used_sd} {r.tokens_per_second:.1f} tok/s  "
              f"{sd}{pf}{timing}")
        if r.steps:
            occ = occupancy_timeline([s.live for s in r.steps],
                                     [s.committed for s in r.steps])
            handoffs = sum(1 for a, b in zip(r.steps, r.steps[1:])
                           if a.used_sd != b.used_sd)
            print(f"  N(t): peak={occ['peak_live']:.0f} "
                  f"mean={occ['mean_live']:.2f} "
                  f"token_weighted={occ['token_weighted_live']:.2f} "
                  f"occupancy={occ['mean_occupancy']:.2f}  "
                  f"admitted={sum(s.admitted for s in r.steps)} "
                  f"retired={sum(s.retired for s in r.steps)} "
                  f"sd_handoffs={handoffs}")
            shared = sum(s.shared_tokens for s in r.steps)
            print(f"  admission: {sum(s.admit_rows for s in r.steps)} "
                  f"prefill rows, {sum(s.admit_tokens for s in r.steps)} "
                  f"row-tokens ({args.admit_mode})"
                  + (f", {shared} prefix-shared tokens" if shared else ""))
        if r.ep is not None:
            # expert-parallel wave telemetry: per-shard routed load of the
            # wave's outputs, skew, and modeled per-device a2a volume
            print(f"  ep: shards={r.ep['per_shard_load']} "
                  f"imbalance={r.ep['imbalance']:.2f} "
                  f"a2a={r.ep['a2a_bytes_per_device'] / 1e6:.3f} MB/device")
    for kind, s in eng.session_stats().items():
        if kind == "resilience":
            if s:                 # fault/preemption/recovery counters
                print("resilience:", " ".join(f"{k}={v}"
                                              for k, v in sorted(s.items())))
            continue
        print(f"session[{kind}]: constructed {s['constructions']}x, "
              f"gammas compiled {s['gammas_compiled']}, "
              f"{len(s['traces'])} round traces, "
              f"{len(s['admit_traces'])} admit traces, "
              f"{len(s['chunk_traces'])} chunk traces, "
              f"{len(s['growths'])} growths")
    sample = eng.done[1]
    print(f"sample completion ({sample.finish_reason}):",
          repr(tok.decode(sample.output)[:80]))

    if args.transfer_guard:
        # warm replay under the runtime guards: the first stream built
        # every program, so this one must move nothing implicitly and
        # keep one input-sharding signature per cached program
        from repro.analysis import sharding_guard, transfer_guard
        submit_poisson(eng, pb["tokens"], pb["lengths"],
                       rate=args.arrival_rate,
                       max_new_choices=max_new_choices, seed=args.seed)
        with transfer_guard() as tg, sharding_guard(eng) as sg:
            eng.run()
        print(f"transfer_guard: {tg.count} implicit transfer(s); "
              f"{sg.render()}")
        if tg.count or not sg.ok:
            for line in tg.lines[:10]:
                print(" ", line)
            raise SystemExit(
                "guard violation on the warm stream replay")


if __name__ == "__main__":
    main()
