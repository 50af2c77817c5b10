"""Heterogeneous decoder block + scan-over-periods stack.

A *block* = mixer sublayer (attention or recurrent) + optional FFN sublayer
(dense or MoE) with pre-norms and residuals.  One *period* of blocks
(``cfg.layer_pattern``) is the scan unit: params/caches carry a leading
``num_periods`` axis, keeping HLO size O(period) instead of O(num_layers) —
essential for 62-layer models compiled against 512 host devices.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.constraints import constrain
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import apply_mlp, apply_norm, init_mlp, init_norm

ATTN_KINDS = ("attn", "swa", "mla")
RECURRENT_KINDS = ("mamba", "mlstm", "slstm")


def _has_ffn(cfg, kind: str, is_moe: bool) -> bool:
    if kind in ("mlstm", "slstm"):
        return False  # xLSTM blocks are self-contained
    return is_moe or cfg.d_ff > 0


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------

def init_block(key, cfg, kind: str, is_moe: bool, dtype, cross: bool = False) -> dict:
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {"norm1": init_norm(cfg, dtype)}
    if kind in ("attn", "swa"):
        p["mixer"] = attn.init_gqa(ks[0], cfg, dtype)
    elif kind == "mla":
        p["mixer"] = attn.init_mla(ks[0], cfg, dtype)
    else:
        p["mixer"] = ssm_mod.INIT[kind](ks[0], cfg, dtype)
    if cross:
        p["cross_norm"] = init_norm(cfg, dtype)
        p["cross"] = attn.init_cross_attn(ks[1], cfg, dtype)
    if _has_ffn(cfg, kind, is_moe):
        p["norm2"] = init_norm(cfg, dtype)
        if is_moe:
            p["ffn"] = moe_mod.init_moe(ks[2], cfg, dtype)
        else:
            p["ffn"] = init_mlp(ks[2], cfg.d_model, cfg.d_ff, dtype)
    return p


def make_block_cache(cfg, kind: str, batch: int, max_seq: int, dtype, *,
                     paged: bool = False, page_size: int = 64,
                     pool_pages: Optional[int] = None) -> dict:
    if kind in ATTN_KINDS:
        return attn.make_attn_cache(cfg, batch, max_seq, kind, dtype,
                                    paged=paged, page_size=page_size,
                                    pool_pages=pool_pages)
    return ssm_mod.MAKE_STATE[kind](cfg, batch, dtype)


def block_forward(
    params: dict,
    cfg,
    kind: str,
    is_moe: bool,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cache: Optional[dict],
    *,
    mode: str,                      # train | prefill | extend
    collect: bool = False,
    causal: bool = True,
    dispatch: str = "onehot",
    want_metrics: bool = True,
    use_flash: bool = False,
    cross_kv: Optional[dict] = None,
    mrope_positions=None,
    prefetch_mask: Optional[jnp.ndarray] = None,
    page_table: Optional[jnp.ndarray] = None,
    paged_attention: str = "kernel",
    mesh=None,
    mesh_layout: Optional[str] = None,
    layer: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Optional[dict], dict]:
    """One block.  ``layer`` (gmm dispatch) is the block's index into the
    whole ``(P, E, ...)`` expert stacks that ``params["ffn"]`` then holds
    (``stack_forward``); None where the experts are this layer's own."""
    h = apply_norm(params["norm1"], x, cfg.norm_eps)
    if kind in ("attn", "swa"):
        out, new_cache = attn.gqa_forward(
            params["mixer"], cfg, h, positions, kind=kind, cache=cache,
            mode=mode, mrope_positions=mrope_positions, use_flash=use_flash,
            causal=causal, page_table=page_table,
            paged_attention=paged_attention, mesh=mesh)
    elif kind == "mla":
        out, new_cache = attn.mla_forward(
            params["mixer"], cfg, h, positions, cache=cache, mode=mode,
            page_table=page_table)
    else:
        state = cache if cache is not None else ssm_mod.MAKE_STATE[kind](
            cfg, x.shape[0], x.dtype)
        out, new_cache = ssm_mod.FORWARD[kind](
            params["mixer"], cfg, h, state, collect_states=(mode == "extend" and collect))
        if mode == "train":
            new_cache = None
    x = x + out

    if "cross" in params and cross_kv is not None:
        h = apply_norm(params["cross_norm"], x, cfg.norm_eps)
        x = x + attn.cross_attn_forward(params["cross"], cfg, h, cross_kv)

    # zero placeholders keep the metrics pytree uniform across layers for the
    # scan aggregation even when metric computation is skipped
    metrics = {"aux_loss": jnp.zeros((), jnp.float32),
               "expert_counts": jnp.zeros((max(cfg.num_experts, 1),), jnp.int32),
               "prefetch_hits": jnp.zeros((), jnp.int32),
               "prefetch_actual": jnp.zeros((), jnp.int32),
               "prefetch_predicted": jnp.zeros((), jnp.int32),
               "experts_reached": jnp.zeros((), jnp.int32)}
    if "ffn" in params:
        h = apply_norm(params["norm2"], x, cfg.norm_eps)
        if is_moe:
            # want_metrics=False (decode/verify) skips the (N, K, E) one-hot
            # aux-loss/expert-count tensors entirely — the router still runs
            # (routing needs it) but no metric materialization happens
            y, m = moe_mod.moe_forward(params["ffn"], cfg, h, dispatch=dispatch,
                                       return_metrics=want_metrics,
                                       prefetch_mask=prefetch_mask,
                                       mesh=mesh, mesh_layout=mesh_layout,
                                       layer=layer)
            metrics.update(m or {})
        else:
            y = apply_mlp(params["ffn"], h, cfg.mlp_activation)
        x = x + y
    return x, new_cache, metrics


# ---------------------------------------------------------------------------
# stacked decoder (scan over periods)
# ---------------------------------------------------------------------------

def init_stack(key, cfg, dtype, cross: bool = False) -> List[dict]:
    """Returns a list (len=period) of per-slot params, leaves stacked over
    the ``num_periods`` axis."""
    P = cfg.num_periods
    out = []
    for i, (kind, is_moe) in enumerate(zip(cfg.layer_pattern, cfg.moe_pattern)):
        keys = jax.random.split(jax.random.fold_in(key, i), P)
        slot = jax.vmap(lambda k: init_block(k, cfg, kind, is_moe, dtype, cross))(keys)
        out.append(slot)
    return out


def make_stack_cache(cfg, batch: int, max_seq: int, dtype, *,
                     paged: bool = False, page_size: int = 64,
                     pool_pages: Optional[int] = None) -> List[dict]:
    P = cfg.num_periods
    if paged and pool_pages is None:
        pool_pages = batch * (-(-max_seq // page_size)) + 1   # + trash page
    out = []
    for kind in cfg.layer_pattern:
        c = make_block_cache(cfg, kind, batch, max_seq, dtype, paged=paged,
                             page_size=page_size, pool_pages=pool_pages)
        out.append(jax.tree.map(lambda a: jnp.broadcast_to(a, (P,) + a.shape), c))
    return out


def stack_forward(
    layer_params: List[dict],
    cfg,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    caches: Optional[List[dict]],
    *,
    mode: str,
    collect: bool = False,
    causal: bool = True,
    dispatch: str = "onehot",
    want_metrics: bool = True,
    use_flash: bool = False,
    remat: bool = False,
    cross_kvs: Optional[List[dict]] = None,
    mrope_positions=None,
    prefetch_masks: Optional[List[jnp.ndarray]] = None,
    page_table: Optional[jnp.ndarray] = None,
    paged_attention: str = "kernel",
    mesh=None,
    mesh_layout: Optional[str] = None,
) -> Tuple[jnp.ndarray, Optional[List[dict]], dict]:
    """Run the full stack.  caches/cross_kvs leaves carry leading (P, ...).

    ``want_metrics=False`` (the serving decode/verify path) skips router
    aux-loss/expert-count materialization; the returned metrics are zeros.

    ``prefetch_masks`` (optional) is a per-period-slot list of ``(P, E)``
    predicted-hot expert masks (models/moe.PrefetchPlan.masks); when given,
    the returned metrics include ``prefetch_hits/actual/predicted`` counts
    summed over all MoE layers.  ``experts_reached`` is always the
    distinct experts each MoE layer's routing reached, summed over the
    layers (zero for the "ep" dispatch, which does not count them).

    ``page_table`` (optional) is the (B, max_pages) logical→physical block
    table of a paged cache (models/model.py) — shared by every paged
    attention slot, carried as a scan closure constant.

    ``paged_attention`` selects the paged extend backend: "kernel" walks the
    block table inside the Pallas decode kernel; "gather" materializes the
    dense ``pool[table]`` view (the pre-kernel behaviour, kept as fallback).

    ``mesh``/``mesh_layout`` (optional) thread the device mesh down to the
    sharding constraints and the expert-parallel dispatch
    (docs/distributed.md) — no process-global mesh state.

    With the "gmm" dispatch the routed experts' ``w_gate``/``w_up``/
    ``w_down`` are scan constants and the period index is scanned: the
    ragged kernels read each layer's experts out of the whole ``(P, E,
    ...)`` stacks (kernels/gmm/ragged.py), where a scanned slice would be
    copied whole before every launch.
    """
    experts = [None] * cfg.period
    if dispatch == "gmm":
        layer_params = list(layer_params)
        for i, is_moe in enumerate(cfg.moe_pattern):
            if is_moe and "ffn" in layer_params[i]:
                ffn = dict(layer_params[i]["ffn"])
                experts[i] = {k: ffn.pop(k)
                              for k in ("w_gate", "w_up", "w_down")}
                layer_params[i] = {**layer_params[i], "ffn": ffn}
    periods = (jnp.arange(cfg.num_periods)
               if any(e is not None for e in experts) else None)

    def make_block(i, kind, is_moe):
        def blk(lp_i, h, lc_i, lx_i, lm_i, layer):
            return block_forward(
                lp_i, cfg, kind, is_moe, h, positions, lc_i,
                mode=mode, collect=collect, causal=causal, dispatch=dispatch,
                want_metrics=want_metrics, use_flash=use_flash, cross_kv=lx_i,
                mrope_positions=mrope_positions, prefetch_mask=lm_i,
                page_table=page_table, paged_attention=paged_attention,
                mesh=mesh, mesh_layout=mesh_layout, layer=layer)
        # per-LAYER rematerialization: checkpointing the whole period keeps
        # every layer's FFN/attention intermediates live during the period's
        # backward (107 GB/device on jamba train_4k — §Perf C4); per-layer
        # checkpoints bound the live set to one layer.
        return jax.checkpoint(blk) if remat else blk

    blocks = [make_block(i, kind, is_moe)
              for i, (kind, is_moe)
              in enumerate(zip(cfg.layer_pattern, cfg.moe_pattern))]

    def period_fn(h, scanned):
        lp, lc, lx, lm, p = scanned
        new_caches = []
        agg = None
        for i in range(cfg.period):
            lp_i, layer = lp[i], None
            if experts[i] is not None:
                lp_i = {**lp_i, "ffn": {**lp_i["ffn"], **experts[i]}}
                layer = p
            h, nc, m = blocks[i](
                lp_i, h,
                None if lc is None else lc[i],
                None if lx is None else lx[i],
                None if lm is None else lm[i], layer)
            new_caches.append(nc if nc is not None else {})
            agg = m if agg is None else jax.tree.map(jnp.add, agg, m)
        return constrain(h, "hidden", mesh=mesh, layout=mesh_layout), \
            (new_caches, agg)

    xs = (layer_params, caches, cross_kvs, prefetch_masks, periods)

    def scan_body(h, scanned):
        return period_fn(h, scanned)

    x, (new_caches, metrics) = jax.lax.scan(scan_body, x, xs)
    metrics = jax.tree.map(lambda a: jnp.sum(a, axis=0), metrics)
    if caches is None:
        return x, None, metrics
    return x, new_caches, metrics
