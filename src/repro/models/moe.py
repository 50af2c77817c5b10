"""Mixture-of-Experts FFN: top-k router, expert dispatch, load-balance loss.

Two dispatch strategies, one interface:

  * ``onehot``  — dense einsum over a (tokens, experts) one-hot combine
                  tensor.  GSPMD-friendly: the expert axis shards cleanly over
                  the ``model`` mesh axis (expert parallelism), XLA turns the
                  dispatch into all-to-all-ish collectives.  Used for
                  training, dry-runs and small tests.
  * ``gmm``     — tokens sorted by expert id, grouped matmul via the Pallas
                  ``gmm`` kernel (MXU-tiled, megablox-style).  Serving path.

The router also reports which experts were activated — the measurement
behind the paper's N(t) validation (Fig. 1a/b).

Expert prefetch (SP-MoE, arXiv:2510.10302): a ``PrefetchPlan`` names, per
period-slot, the experts a router probe over the draft token stream predicts
the next verify pass will hit.  ``warm_experts`` gathers exactly those
experts' FFN weights into fresh device buffers — dispatched during the SD
propose phase, so on an accelerator the HBM reads of the predicted experts
overlap drafting instead of serializing with verify.  ``moe_forward``
accepts the per-slot mask and scores the prediction against the experts the
verify pass actually activated (hit/miss counts, surfaced per wave by the
serving engine).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init


class PrefetchPlan(NamedTuple):
    """Per-period-slot expert-warmup prediction (a jit-safe pytree).

    Attributes
    ----------
    masks : tuple of jnp.ndarray
        One ``(P, E)`` bool array per period-slot (``P`` = num_periods,
        ``E`` = num_experts): True where the probe predicts the verify pass
        will activate that expert.  Non-MoE slots carry all-False masks.
    expert_ids : tuple of jnp.ndarray
        One ``(P, M)`` int32 array per period-slot — the top-M predicted
        expert ids backing each mask (``M`` static, so the warm gather has a
        fixed shape).  Non-MoE slots carry ``(P, 0)``.
    """

    masks: Tuple[jnp.ndarray, ...]
    expert_ids: Tuple[jnp.ndarray, ...]


# lint: allow[D602] prefetch is simulation-only until gmm takes donated buffers
def warm_experts(layer_params, cfg, plan: PrefetchPlan, *, mesh=None):
    """Gather the predicted experts' FFN weights into fresh buffers.

    Parameters
    ----------
    layer_params : list of dict
        ``params["layers"]`` — per period-slot params with leading ``P``
        axis (``w_gate``/``w_up``/``w_down`` are ``(P, E, d, f)``-shaped).
    cfg : ModelConfig
        Supplies ``moe_pattern`` (which slots have routed FFNs).
    plan : PrefetchPlan
        ``expert_ids[i]`` selects the ``(P, M)`` experts to warm in slot i.
    mesh : jax.sharding.Mesh, optional
        When the expert weights are sharded over a ``"model"`` axis
        (expert-parallel serving), the gather runs under shard_map so each
        shard warms ONLY the predicted ids in its LOCAL expert slice —
        warming never all-gathers remote experts' weights.

    Returns
    -------
    list of dict
        Per MoE slot, ``{"w_gate": (P, M, d, f), "w_up": ..., "w_down":
        (P, M, f, d)}`` gathered copies (an extra leading shard axis under
        a mesh, non-local ids zeroed).  The VALUES are not consumed — the
        point is the dispatch: issued right after propose, the gather
        streams the predicted experts' weights while the host is still
        assembling the verify launch.  NOTE this makes the warming a
        dispatch-level SIMULATION in this reproduction: verify still reads
        the original buffers, so the priced k2 saving (docs/metrics.md) is
        a model of what the measured hit rate is worth once warmed buffers
        are donated to the gmm dispatch (ROADMAP headroom).
    """
    ep = (mesh is not None and "model" in getattr(mesh, "axis_names", ())
          and mesh.shape["model"] > 1
          and cfg.num_experts % mesh.shape["model"] == 0)
    if ep:
        from jax.sharding import PartitionSpec as SP

        def _local_gather(w, ids):
            # w: (P, e_local, ...) LOCAL slice; ids: (P, M) global ids.
            # Gather only the ids this shard owns; foreign ids read row 0
            # of the local slice (free) and are zeroed.
            e_local = w.shape[1]
            first = jax.lax.axis_index("model") * e_local
            mine = (ids >= first) & (ids < first + e_local)
            lids = jnp.clip(ids - first, 0, e_local - 1)
            g = jax.vmap(lambda wp, ip: jnp.take(wp, ip, axis=0))(w, lids)
            g = g * mine[..., None, None].astype(g.dtype)
            return g[None]                       # stack shard results

        def gather(w, ids):
            nd = w.ndim
            return jax.shard_map(
                _local_gather, mesh=mesh,
                in_specs=(SP(None, "model", *([None] * (nd - 2))), SP()),
                out_specs=SP("model", *([None] * nd)),
                check_vma=False)(w, ids)
    else:
        gather = jax.vmap(lambda w, ids: jnp.take(w, ids, axis=0))
    warmed = []
    for i, is_moe in enumerate(cfg.moe_pattern):
        if not is_moe or plan.expert_ids[i].shape[-1] == 0:
            continue
        ffn = layer_params[i]["ffn"]
        ids = plan.expert_ids[i]
        warmed.append({k: gather(ffn[k], ids)
                       for k in ("w_gate", "w_up", "w_down")})
    return warmed


def reached_experts(indices: jnp.ndarray, num_experts: int) -> jnp.ndarray:
    """``(E,)`` bool: the experts that ``(N, K)`` routed ids reach."""
    return jnp.zeros((num_experts,), bool).at[indices.reshape(-1)].set(True)


def prefetch_hit_stats(prefetch_mask: jnp.ndarray,
                       reached: jnp.ndarray) -> dict:
    """Score one layer's prediction against the experts actually routed to.

    Parameters
    ----------
    prefetch_mask : jnp.ndarray
        ``(E,)`` bool — experts the plan predicted (and warmed).
    reached : jnp.ndarray
        ``(E,)`` bool — experts this forward's routing reached, the mask
        behind ``moe_forward``'s ``experts_reached`` count.

    Returns
    -------
    dict
        int32 scalars: ``hits`` (activated AND warmed), ``actual``
        (activated), ``predicted`` (warmed) — the per-wave hit/miss
        accounting aggregated by the engine.
    """
    predicted = prefetch_mask.astype(bool)
    return {
        "prefetch_hits": jnp.sum(reached & predicted).astype(jnp.int32),
        "prefetch_actual": jnp.sum(reached).astype(jnp.int32),
        "prefetch_predicted": jnp.sum(predicted).astype(jnp.int32),
    }


def init_moe(key, cfg, dtype) -> dict:
    d, f = cfg.d_model, cfg.moe_d_ff
    E = cfg.num_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, E), jnp.float32),  # router in fp32
        "w_gate": dense_init(ks[1], (E, d, f), dtype),
        "w_up": dense_init(ks[2], (E, d, f), dtype),
        "w_down": dense_init(ks[3], (E, f, d), dtype),
    }
    if cfg.num_shared_experts:
        fs = f * cfg.num_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": dense_init(k1, (d, fs), dtype),
            "w_up": dense_init(k2, (d, fs), dtype),
            "w_down": dense_init(k3, (fs, d), dtype),
        }
    return p


def _act(x, activation: str):
    return jax.nn.gelu(x, approximate=True) if activation == "gelu" else jax.nn.silu(x)


def router_topk(
    params: dict, cfg, x: jnp.ndarray, rng: Optional[jax.Array] = None
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: (N, d) → (weights (N,K), indices (N,K), router_probs (N,E))."""
    logits = x.astype(jnp.float32) @ params["router"]
    if cfg.router_jitter > 0 and rng is not None:
        logits = logits + cfg.router_jitter * jax.random.normal(rng, logits.shape)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, indices = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, indices, probs


def load_balance_loss(probs: jnp.ndarray, indices: jnp.ndarray, num_experts: int) -> jnp.ndarray:
    """Switch-transformer aux loss: E * sum_e f_e * P_e  (arXiv:2101.03961)."""
    one_hot = jax.nn.one_hot(indices, num_experts, dtype=jnp.float32)   # (N,K,E)
    f = jnp.mean(jnp.sum(one_hot, axis=1), axis=0)                      # fraction per expert
    p = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(f * p)


def expert_activation_counts(indices: jnp.ndarray, num_experts: int) -> jnp.ndarray:
    """Tokens routed to each expert — the paper's N(t)/T̄_exp measurement."""
    one_hot = jax.nn.one_hot(indices, num_experts, dtype=jnp.int32)
    return jnp.sum(one_hot, axis=tuple(range(one_hot.ndim - 1)))


def _dispatch_onehot(params, cfg, x, weights, indices):
    """(N,d) → (N,d) via dense one-hot combine.  Experts axis = leading dim of
    w_*: shards over the `model` mesh axis → expert parallelism under GSPMD."""
    E = cfg.num_experts
    combine = jnp.einsum(
        "nk,nke->ne", weights, jax.nn.one_hot(indices, E, dtype=weights.dtype)
    )                                                      # (N, E)
    # per-expert FFN on every token, weighted combine (dense but shardable)
    h_gate = jnp.einsum("nd,edf->enf", x, params["w_gate"])
    h_up = jnp.einsum("nd,edf->enf", x, params["w_up"])
    h = _act(h_gate, cfg.mlp_activation) * h_up
    y = jnp.einsum("enf,efd->end", h, params["w_down"])    # (E,N,d)
    return jnp.einsum("end,ne->nd", y, combine.astype(y.dtype))


def _dispatch_gmm(params, cfg, x, weights, indices, group_sizes, layer=None):
    """Sort tokens by expert, ragged grouped matmul (kernels/gmm/ragged.py):
    fused gate+up launch then down launch — 2 Pallas calls per MoE FFN, and
    expert GEMM work scales with the routed token count N*K, not E*C.
    ``group_sizes`` (E,) is the routed-row count per expert; ``layer``
    indexes stacked ``(P, E, ...)`` expert weights inside the kernels."""
    from repro.kernels.gmm import ops as gmm_ops

    N, d = x.shape
    K = cfg.num_experts_per_tok
    flat_expert = indices.reshape(-1)                       # (N*K,)
    order = jnp.argsort(flat_expert)
    token_of = order // K                                   # source token per slot
    xs = x[token_of]                                        # (N*K, d) sorted by expert

    ys = gmm_ops.ragged_moe_ffn(
        xs, params["w_gate"], params["w_up"], params["w_down"], group_sizes,
        layer=layer, activation=cfg.mlp_activation)         # (N*K, d)

    w_flat = weights.reshape(-1)[order].astype(ys.dtype)    # (N*K,)
    out = jnp.zeros((N, d), ys.dtype)
    return out.at[token_of].add(ys * w_flat[:, None])


def moe_forward(
    params: dict,
    cfg,
    x: jnp.ndarray,                  # (B, T, d)
    *,
    dispatch: str = "onehot",        # "onehot" | "gmm" | "ep"
    rng: Optional[jax.Array] = None,
    return_metrics: bool = False,
    prefetch_mask: Optional[jnp.ndarray] = None,   # (E,) predicted-hot experts
    mesh=None,
    mesh_layout: Optional[str] = None,
    layer: Optional[jnp.ndarray] = None,
):
    """Routed MoE FFN: top-k route, dispatch to experts, weighted combine.

    Parameters
    ----------
    params : dict
        ``init_moe`` params (router + per-expert FFN weights).
    cfg : ModelConfig
        Supplies E, K, activation, jitter.
    x : jnp.ndarray
        (B, T, d) input activations.
    dispatch : str
        "onehot" (dense, shardable — training), "gmm" (ragged grouped
        matmul — serving) or "ep" (expert-parallel shard_map).  Tradeoffs
        in docs/dispatch.md.
    rng : jax.Array, optional
        Router jitter key (train only).
    return_metrics : bool
        Compute aux-loss / expert-count metrics (train only — materializes
        (N, K, E) one-hots).
    prefetch_mask : jnp.ndarray, optional
        (E,) predicted-hot expert mask from a PrefetchPlan; when given, the
        returned metrics include prefetch hit/miss counts scored against
        this forward's actual routing (``prefetch_actual`` is
        ``experts_reached``).
    mesh, mesh_layout : optional
        Device mesh (and layout) threaded explicitly to the "ep" dispatch
        and ignored by the single-device dispatches — see
        docs/distributed.md.
    layer : jnp.ndarray, optional
        "gmm" only: the int32 index of this layer when ``w_gate``/``w_up``/
        ``w_down`` are the whole ``(P, E, ...)`` layer stacks, which the
        kernels then read at that index (no per-layer slice).  None for
        per-layer ``(E, ...)`` weights.

    Returns
    -------
    (jnp.ndarray, dict or None)
        (B, T, d) output and the metrics.  The "onehot" and "gmm"
        dispatches always report ``experts_reached``: the int32 count of
        distinct experts the routing of all B*T rows reaches (padding
        rows included — the expert FFN runs them too), the experts whose
        weights the ragged gmm reads.  "ep" reports it only with a
        ``prefetch_mask`` (as ``prefetch_actual``, from a re-route), and
        returns None when neither ``return_metrics`` nor
        ``prefetch_mask`` is given.
    """
    B, T, d = x.shape
    if dispatch == "ep":
        # expert-parallel shard_map path (distributed/collectives.py);
        # router runs inside the shard, so metrics (and prefetch scoring)
        # come from a cheap replicated re-route below.
        from repro.distributed.collectives import moe_ep_forward
        y = moe_ep_forward(params, cfg, x, mesh=mesh, layout=mesh_layout)
        metrics = None
        if return_metrics or prefetch_mask is not None:
            xf = x.reshape(B * T, d)
            _, indices, probs = router_topk(params, cfg, xf, rng)
            if return_metrics:
                metrics = {
                    "aux_loss": load_balance_loss(probs, indices,
                                                  cfg.num_experts),
                    "expert_counts": expert_activation_counts(
                        indices, cfg.num_experts),
                }
            if prefetch_mask is not None:
                metrics = dict(metrics or {}, **prefetch_hit_stats(
                    prefetch_mask, reached_experts(indices, cfg.num_experts)))
        return y, metrics
    xf = x.reshape(B * T, d)
    weights, indices, probs = router_topk(params, cfg, xf, rng)
    if dispatch == "gmm":
        group_sizes = jnp.bincount(indices.reshape(-1),
                                   length=cfg.num_experts)
        y = _dispatch_gmm(params, cfg, xf, weights.astype(x.dtype), indices,
                          group_sizes, layer)
        reached = group_sizes > 0
    else:
        y = _dispatch_onehot(params, cfg, xf, weights.astype(x.dtype), indices)
        reached = reached_experts(indices, cfg.num_experts)
    if "shared" in params:
        s = params["shared"]
        h = _act(xf @ s["w_gate"], cfg.mlp_activation) * (xf @ s["w_up"])
        y = y + h @ s["w_down"]
    y = y.reshape(B, T, d)
    metrics = {"experts_reached": jnp.sum(reached).astype(jnp.int32)}
    if return_metrics:
        metrics["aux_loss"] = load_balance_loss(probs, indices,
                                                cfg.num_experts)
        metrics["expert_counts"] = expert_activation_counts(indices,
                                                            cfg.num_experts)
    if prefetch_mask is not None:
        # score the warm plan against the experts this forward actually
        # reached — decode-only, train never passes a mask
        metrics.update(prefetch_hit_stats(prefetch_mask, reached))
    return y, metrics
