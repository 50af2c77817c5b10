"""Sharding rules: parameter / cache / activation PartitionSpecs.

Logical placement (DESIGN.md §7):

  * tensor-parallel ("model" axis):  attention heads, FFN hidden dim,
    vocab dim of embed/head; MoE experts are EXPERT-parallel — the expert
    axis shards over "model", the paper's EP deployment (§3.4: EP changes
    neither N(t) nor T̄_exp, so the MoESD analysis carries over unchanged).
  * batch-parallel ("pod","data"): batch dim of activations and caches.
  * FSDP (train mode): parameters additionally shard their largest
    remaining dim over ("pod","data"); optimizer moments inherit.

Every rule degrades gracefully: if a dim is not divisible by the axis size
the axis is dropped (replicated) — this is what lets all 40 arch x shape
combinations lower on the same mesh without per-arch special-casing.

Scan-stacked layer params carry a leading (num_periods,) axis → specs are
prefixed with None.
"""
from __future__ import annotations

import re
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    import math
    return math.prod(mesh.shape[a] for a in axes)


def _fit(mesh: Mesh, spec: P, shape) -> P:
    """Drop sharded axes whose dim is not divisible by the axis size."""
    out = []
    for dim, axes in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if axes is None:
            out.append(None)
        elif dim % _axis_size(mesh, axes) == 0 and dim > 0:
            out.append(axes)
        else:
            out.append(None)
    return P(*out)


# ---------------------------------------------------------------------------
# parameter rules
# ---------------------------------------------------------------------------

# (path regex, spec WITHOUT the leading scan axis). First match wins.
# "D" placeholder = FSDP axes in train mode, None in serve mode.
_PARAM_RULES = [
    # MoE experts: expert-parallel over "model"; FSDP over the big d_ff dim
    (r"ffn/(w_gate|w_up|w_down)$", ("model", None, "D")),
    (r"ffn/router$", (None, None)),
    (r"shared/(w_gate|w_up)$", ("D", "model")),
    (r"shared/w_down$", ("model", "D")),
    # dense FFN: megatron column/row split
    (r"(^|/)(w_gate|w_up|w_ffn_up)$", ("D", "model")),
    (r"(^|/)(w_down|w_ffn_down)$", ("model", "D")),
    # attention projections
    (r"(wq|wk|wv|w_uq|w_uk|w_uv)$", ("D", "model")),
    (r"(wo)$", ("model", "D")),
    (r"(bq|bk|bv)$", ("model",)),
    (r"(w_dkv|w_dq)$", ("D", None)),
    # ssm / xlstm
    (r"mixer/w_in$", ("D", "model")),
    (r"mixer/(w_out|w_down)$", ("model", "D")),
    (r"mixer/w_up$", ("D", "model")),
    (r"mixer/(conv_w|conv_b)$", (None,)),
    (r"mixer/(w_xdbc|w_dt|A_log|dt_bias|D)$", ("model",)),
    (r"mixer/(w_i|w_f|i_bias|f_bias)$", (None,)),
    (r"(r_z|r_i|r_f|r_o|w_z)$", ("D", "model")),
    # embeddings / head: vocab over model
    (r"(embed|head)/table$", ("model", "D")),
    # norms & everything small: replicated
    (r".*", (None,)),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_spec(path_str: str, shape, *, mesh: Mesh, fsdp: bool,
               stacked: bool, fsdp_min_size: int = 0,
               layout: str = "tp") -> P:
    """layout:
      "tp"   — Megatron TP over "model" + FSDP over ("pod","data")  [default]
      "fsdp" — no tensor parallelism: every axis (incl. "model") is a batch/
               FSDP axis; dense weights shard their FSDP dim over ALL axes.
               Trades per-layer activation all-reduces for parameter
               all-gathers — wins when tokens/step ≫ params (§Perf B1)."""
    import math
    def _matches(pat, spec):
        """Rule applies if the pattern hits AND the spec rank fits the leaf
        (dense FFN and MoE expert weights share ffn/w_* paths; ranks differ:
        2D dense vs 3D (E, d, f) experts)."""
        if not re.search(pat, path_str):
            return False
        want = len(spec) + (1 if stacked else 0)
        return want <= len(shape) or len(spec) <= 1

    if layout == "fsdp":
        all_axes = tuple(mesh.axis_names)
        d_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
        n_elem = math.prod(shape) if shape else 0
        ok = fsdp and n_elem >= fsdp_min_size
        for pat, spec in _PARAM_RULES:
            if _matches(pat, spec):
                # MoE expert weights ("model", None, "D"): experts STAY on
                # the model axis (EP needs it), FSDP over the data axes.
                is_expert = len(spec) == 3 and spec[0] == "model"
                resolved = []
                assigned = False
                for s in spec:
                    if is_expert:
                        if s == "model":
                            resolved.append("model")
                        elif s == "D":
                            resolved.append(d_axes if ok else None)
                        else:
                            resolved.append(None)
                    elif s in ("model", "D") and not assigned:
                        # dense weights: no TP — one dim shards over ALL axes
                        resolved.append(all_axes if ok else None)
                        assigned = True
                    else:
                        resolved.append(None)
                if stacked:
                    resolved = [None] + resolved
                return _fit(mesh, P(*resolved), shape)
        return P()
    d_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    n_elem = math.prod(shape) if shape else 0
    fs = d_axes if (fsdp and d_axes and n_elem >= fsdp_min_size) else None
    for pat, spec in _PARAM_RULES:
        if _matches(pat, spec):
            resolved = tuple(fs if s == "D" else s for s in spec)
            if stacked:
                resolved = (None,) + resolved
            return _fit(mesh, P(*resolved), shape)
    return P()


def shard_params(params, mesh: Mesh, *, fsdp: bool = False,
                 fsdp_min_size: int = 0, layout: str = "tp"):
    """Pytree of NamedSharding for a params tree (layers/* are scan-stacked).

    ``fsdp_min_size``: leaves smaller than this (elements) skip the FSDP
    axis — small weights are cheaper to replicate than to all-gather every
    layer (a §Perf lever)."""

    def spec_of(path, leaf):
        ps = _path_str(path)
        stacked = ps.startswith("layers") or "/layers/" in ps
        return NamedSharding(mesh, param_spec(ps, leaf.shape, mesh=mesh,
                                              fsdp=fsdp, stacked=stacked,
                                              fsdp_min_size=fsdp_min_size,
                                              layout=layout))

    return jax.tree_util.tree_map_with_path(spec_of, params)


# ---------------------------------------------------------------------------
# cache rules
# ---------------------------------------------------------------------------

def cache_spec(path_str: str, shape, *, mesh: Mesh, kv_mode: str = "auto") -> P:
    """KV/state caches: leading (P periods, B, ...).

    Batch shards over ("pod","data") when divisible.  ``kv_mode``:
      auto  — head axis over "model" when divisible, else sequence axis
              (flash-decoding style; XLA inserts the partial-softmax combine)
      seq   — always shard the sequence axis over "model"
      heads — shard heads (replicating when non-divisible)"""
    d_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data")) or None
    msize = mesh.shape["model"]
    if re.search(r"lengths$", path_str):
        return _fit(mesh, P(d_axes), shape)
    if re.search(r"pages/table$", path_str):
        # (B, max_pages) logical→physical block table: rows follow batch
        return _fit(mesh, P(d_axes, None), shape)
    if re.search(r"(k|v)_pages$", path_str) and len(shape) == 5:
        # (P, pool, Hkv, page, hd) head-major page pool: pages are a SHARED
        # pool addressed through the table (page ids carry no batch
        # locality), so only the head axis shards — over "model"
        return _fit(mesh, P(None, None, "model", None, None), shape)
    if re.search(r"(latent|k_rope)_pages$", path_str):
        # (P, pool, page, dim) MLA page pools: replicated pool
        return P(*([None] * len(shape)))
    if re.search(r"(^|/)(k|v)$", path_str) and len(shape) == 5:
        # (P, B, S, Hkv, hd)
        head_ok = shape[3] % msize == 0
        if kv_mode == "seq" or (kv_mode == "auto" and not head_ok):
            return _fit(mesh, P(None, d_axes, "model", None, None), shape)
        return _fit(mesh, P(None, d_axes, None, "model", None), shape)
    if re.search(r"pos$", path_str) and len(shape) == 3:
        return _fit(mesh, P(None, d_axes, None), shape)
    if re.search(r"(latent|k_rope)$", path_str) and len(shape) == 4:
        return _fit(mesh, P(None, d_axes, "model", None), shape)   # seq-sharded
    if re.search(r"(^|/)(conv|ssm|C|n|m|c|h)$", path_str):
        # recurrent states: (P, B, ...) — shard batch; biggest state dim on model
        spec = [None, d_axes] + [None] * (len(shape) - 2)
        for i in range(2, len(shape)):
            if shape[i] % msize == 0:
                spec[i] = "model"
                break
        return _fit(mesh, P(*spec), shape)
    return _fit(mesh, P(None, d_axes), shape)


def shard_cache(cache, mesh: Mesh, kv_mode: str = "auto"):
    """NamedSharding pytree for a session KV cache: batch dims over the
    data axes, KV heads (dense layers and paged pools) over "model" where
    divisible; one ``cache_spec`` rule per leaf path."""
    def spec_of(path, leaf):
        return NamedSharding(mesh, cache_spec(_path_str(path), leaf.shape,
                                              mesh=mesh, kv_mode=kv_mode))

    return jax.tree_util.tree_map_with_path(spec_of, cache)


# ---------------------------------------------------------------------------
# activations / batches / optimizer state
# ---------------------------------------------------------------------------

def batch_sharding(mesh: Mesh, tree, layout: str = "tp"):
    """tokens/labels/mask (B, T) and embeds (B, T, d): batch over data axes
    (every axis in the "fsdp" layout)."""
    if layout == "fsdp":
        d_axes = tuple(mesh.axis_names) or None
    else:
        d_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data")) or None

    def spec_of(path, leaf):
        spec = P(d_axes, *([None] * (leaf.ndim - 1)))
        return NamedSharding(mesh, _fit(mesh, spec, leaf.shape))

    return jax.tree_util.tree_map_with_path(spec_of, tree)


def shard_opt_state(opt_state, params_shardings, mesh: Mesh):
    """Adam moments inherit parameter shardings; step is replicated."""
    from repro.training.optimizer import AdamState
    return AdamState(
        step=NamedSharding(mesh, P()),
        mu=params_shardings,
        nu=params_shardings,
    )
