import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^^ MUST precede every other import: jax locks the device count at first
# backend initialization, and the production dry-run needs 512 placeholder
# host devices to build the 16x16 / 2x16x16 meshes.  (Never set globally —
# smoke tests and benches must see 1 device.)

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination with production shardings, WITHOUT allocating a single real
array (ShapeDtypeStruct stand-ins all the way).

Per combination this emits: memory_analysis (fits/doesn't), cost_analysis
FLOPs/bytes, and the collective-byte census parsed from the partitioned
HLO — the three roofline terms of EXPERIMENTS.md §Roofline.

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod --out results/
"""
import argparse
import json
import re
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import INPUT_SHAPES, ModelConfig, ShapeConfig, TrainConfig
from repro.configs.registry import ASSIGNED, draft_for, get_config
from repro.distributed.sharding import (
    batch_sharding, shard_cache, shard_opt_state, shard_params)
from repro.launch.mesh import make_production_mesh
from repro.models.model import Model
from repro.serving.serve_step import make_decode_step, make_prefill_step, make_verify_step
from repro.training.optimizer import init_adam
from repro.training.train_loop import make_train_step

from repro.launch.specs import (SWA_VARIANT_WINDOW, arch_for_shape, input_specs, sds)

# ---------------------------------------------------------------------------
# lowering
# ---------------------------------------------------------------------------

def hlo_cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()``: one dict of HLO cost totals."""
    return compiled.cost_analysis()


def _collective_bytes(hlo_text: str) -> dict:
    """Collective census over partitioned HLO.

    Sums operand bytes of every collective op, attributed to whether the op
    sits inside a while-loop body (the layer-stack scan: executes
    ``num_periods`` times — multiplied by the trip count downstream in
    launch/roofline.py) or in the entry computation (executes once, e.g.
    hoisted FSDP all-gathers)."""
    dt_bytes = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f64": 8, "s16": 2,
                "u16": 2, "c64": 8, "f8e4m3fn": 1, "f8e5m2": 1}
    name_bytes = {}
    op_re = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^)]*\)|\S+?)\s+([\w\-]+)\(")
    comp_re = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\([^=]*\)\s*->.*\{")
    body_re = re.compile(r"body=%?([\w.\-]+)")
    type_re = re.compile(r"(\w+?)\[([\d,]*)\]")

    def type_bytes(tstr: str) -> int:
        total = 0
        for m in type_re.finditer(tstr):
            dt, dims = m.group(1), m.group(2)
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            total += n * dt_bytes.get(dt, 4)
        return total

    lines = hlo_text.splitlines()
    body_names = set()
    for ln in lines:
        for m in body_re.finditer(ln):
            body_names.add(m.group(1))

    ops = []
    current_comp = ""
    for ln in lines:
        cm = comp_re.match(ln)
        if cm and ln.rstrip().endswith("{"):
            current_comp = cm.group(1)
            continue
        m = op_re.match(ln)
        if not m:
            continue
        name, tstr, opcode = m.groups()
        name_bytes[name] = type_bytes(tstr)
        ops.append((name, opcode, ln, current_comp))

    kinds = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
    out = {k: 0 for k in kinds}
    out["in_loop"] = 0
    out["outside"] = 0
    operand_re = re.compile(r"%?([\w.\-]+)")
    for name, opcode, ln, comp in ops:
        kind = next((k for k in kinds if opcode.startswith(k)), None)
        if kind is None:
            continue
        args = ln.split("(", 1)[1].split(")")[0]
        ob = 0
        for tok in args.split(","):
            tok = tok.strip()
            m = operand_re.match(tok.lstrip("%"))
            if m and m.group(1) in name_bytes:
                ob += name_bytes[m.group(1)]
        ob = ob if ob else name_bytes.get(name, 0)
        out[kind] += ob
        if comp in body_names:
            out["in_loop"] += ob
        else:
            out["outside"] += ob
    out["total"] = sum(out[k] for k in kinds)
    return out


def lower_combo(arch: str, shape_name: str, *, multi_pod: bool = False,
                gamma: int = 0, donate: bool = True,
                moe_dispatch: str = "onehot",
                fsdp_min_size: int = 0,
                kv_mode: str = "auto",
                layout: str = "tp",
                remat: Optional[bool] = None,
                extra_overrides: Optional[dict] = None) -> dict:
    shape = INPUT_SHAPES[shape_name]
    cfg = arch_for_shape(arch, shape, gamma)
    if extra_overrides:
        cfg = cfg.with_overrides(**extra_overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = Model(cfg, moe_dispatch=moe_dispatch,
                  remat=(shape.kind == "train") if remat is None else remat,
                  mesh=mesh, mesh_layout=layout)

    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params_sh = shard_params(params_shape, mesh, fsdp=(shape.kind == "train"),
                             fsdp_min_size=fsdp_min_size, layout=layout)
    specs = input_specs(cfg, shape, model, gamma)
    t0 = time.time()

    with mesh:
        if shape.kind == "train":
            opt_shape = jax.eval_shape(init_adam, params_shape)
            opt_sh = shard_opt_state(opt_shape, params_sh, mesh)
            batch_sh = batch_sharding(mesh, specs["batch"], layout=layout)
            step = make_train_step(model, TrainConfig())
            jitted = jax.jit(step,
                             in_shardings=(params_sh, opt_sh, batch_sh),
                             out_shardings=(params_sh, opt_sh, None),
                             donate_argnums=(0, 1) if donate else ())
            lowered = jitted.lower(params_shape, opt_shape, specs["batch"])
        elif shape.kind == "prefill":
            cache_sh = shard_cache(specs["cache"], mesh, kv_mode=kv_mode)
            tok_sh = batch_sharding(mesh, specs["tokens"])
            len_sh = batch_sharding(mesh, specs["lengths"])
            step = make_prefill_step(model)
            if cfg.is_encoder_decoder:
                enc_sh = batch_sharding(mesh, specs["encoder_embeds"])
                jitted = jax.jit(
                    lambda p, t, c, l, e: step(p, t, c, lengths=l,
                                               encoder_embeds=e),
                    in_shardings=(params_sh, tok_sh, cache_sh, len_sh, enc_sh),
                    donate_argnums=(2,) if donate else ())
                lowered = jitted.lower(params_shape, specs["tokens"],
                                       specs["cache"], specs["lengths"],
                                       specs["encoder_embeds"])
            else:
                jitted = jax.jit(
                    lambda p, t, c, l: step(p, t, c, lengths=l),
                    in_shardings=(params_sh, tok_sh, cache_sh, len_sh),
                    donate_argnums=(2,) if donate else ())
                lowered = jitted.lower(params_shape, specs["tokens"],
                                       specs["cache"], specs["lengths"])
        else:  # decode
            cache_sh = shard_cache(specs["cache"], mesh, kv_mode=kv_mode)
            if gamma > 0:
                step = make_verify_step(model, gamma)
                tok_sh = batch_sharding(mesh, specs["tokens"])
                n_sh = batch_sharding(mesh, specs["n_commit"])
                jitted = jax.jit(step,
                                 in_shardings=(params_sh, tok_sh, n_sh, cache_sh),
                                 out_shardings=(None, cache_sh),
                                 donate_argnums=(3,) if donate else ())
                lowered = jitted.lower(params_shape, specs["tokens"],
                                       specs["n_commit"], specs["cache"])
            else:
                step = make_decode_step(model)
                tok_sh = batch_sharding(mesh, specs["token"])
                jitted = jax.jit(step,
                                 in_shardings=(params_sh, tok_sh, cache_sh),
                                 out_shardings=(None, cache_sh),
                                 donate_argnums=(2,) if donate else ())
                lowered = jitted.lower(params_shape, specs["token"],
                                       specs["cache"])

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = hlo_cost_analysis(compiled)
    coll = _collective_bytes(compiled.as_text())
    n_dev = mesh.devices.size
    result = {
        "arch": arch, "config": cfg.name, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "devices": int(n_dev),
        "gamma": gamma,
        "params": int(cfg.param_count()),
        "active_params": int(cfg.active_param_count()),
        "flops_per_device": float(cost.get("flops", -1.0)),
        "bytes_per_device": float(cost.get("bytes accessed", -1.0)),
        "collective_bytes_per_device": coll,
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            # some backends report 0 peak; fall back to the conservative
            # bound arguments + outputs + temporaries all live at once
            "peak_bytes": int(
                getattr(mem, "peak_memory_in_bytes", 0) or 0) or (
                int(getattr(mem, "argument_size_in_bytes", 0))
                + int(getattr(mem, "output_size_in_bytes", 0))
                + int(getattr(mem, "temp_size_in_bytes", 0))),
        },
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "knobs": {"moe_dispatch": moe_dispatch, "kv_mode": kv_mode,
                  "fsdp_min_size": fsdp_min_size, "layout": layout},
    }
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--gamma", type=int, default=0,
                    help=">0 lowers the SD verify step instead of AR decode")
    ap.add_argument("--moe-dispatch", default="onehot")
    ap.add_argument("--fsdp-min-size", type=int, default=0)
    ap.add_argument("--kv-mode", default="auto", choices=["auto", "seq", "heads"])
    ap.add_argument("--layout", default="tp", choices=["tp", "fsdp"])
    ap.add_argument("--out", default=None, help="append JSONL here")
    args = ap.parse_args()

    combos = []
    if args.all:
        for a in ASSIGNED:
            for s in INPUT_SHAPES:
                combos.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos = [(args.arch, args.shape)]

    for arch, shape in combos:
        try:
            res = lower_combo(arch, shape, multi_pod=args.multi_pod,
                              gamma=args.gamma, moe_dispatch=args.moe_dispatch,
                              fsdp_min_size=args.fsdp_min_size,
                              kv_mode=args.kv_mode, layout=args.layout)
            res["status"] = "ok"
        except Exception as e:  # noqa: BLE001 — report, don't abort the sweep
            res = {"arch": arch, "shape": shape,
                   "mesh": "2x16x16" if args.multi_pod else "16x16",
                   "status": "fail", "error": f"{type(e).__name__}: {e}"}
        print(json.dumps(res))
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(res) + "\n")


if __name__ == "__main__":
    main()
