"""Expert-parallel MoE dispatch via shard_map (the §Perf optimization).

The baseline one-hot dispatch (models/moe.py) runs EVERY token through
EVERY expert — E/K-fold redundant compute (usefulness ≈ K/E in the
roofline table) that GSPMD cannot eliminate.  This module dispatches
routed tokens through an explicit two-hop ``all_to_all`` into the
*per-shard ragged grouped matmul*:

  * expert weights are sharded over the "model" axis (E/m experts/shard),
  * token rows are sharded over EVERY mesh axis (each of the n shards
    routes a disjoint slice),
  * each shard ranks its (token, k) pairs per destination shard and
    all-to-alls the token payloads to the shards owning the chosen
    experts — per-DEST-shard slot buffers, NOT dense (E, C) capacity
    bins,
  * the receiving shard sorts arrivals by LOCAL expert id and runs the
    ragged gmm kernel (kernels/gmm/ragged.py) with local group sizes —
    expert GEMM work scales with the tokens actually received, and an
    EMPTY local expert costs zero tiles,
  * the shared-expert matmul runs on local rows BETWEEN the two a2a hops,
    so the combine is staggered and the compiler can hide the collectives
    under independent compute (the TensorRT-LLM NCCL-overlap idiom),
  * the return all-to-all brings each pair's expert output home, where it
    is combined against the top-k router weights.

Per-layer collective cost: 2 × (N·K·d / ep_degree) elements per device
(dispatch + combine) — priced by ``SpeedupModel.ep_a2a_time``
(core/perf_model.py) and reported per wave by ``ep_load_report``.

Used with ``Model(cfg, moe_dispatch="ep", mesh=...)``; the mesh is
threaded explicitly (docs/distributed.md) — the old ``constraints.set_mesh``
process-global is removed.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.constraints import resolve_mesh
from repro.kernels import resolve_interpret


def _act(x, activation):
    return jax.nn.gelu(x, approximate=True) if activation == "gelu" else jax.nn.silu(x)


def _ragged_ep_shard(x, router_w, w_gate, w_up, w_down, shared, *,
                     cfg, slots: int, activation: str,
                     model_axis: str, m_shards: int, interpret):
    """shard_map body: route local rows, all-to-all routed payloads to the
    shards owning the chosen experts, run the LOCAL ragged gmm slice, and
    all-to-all the results back for the top-k weighted combine.

    x: (N_loc, d) this shard's disjoint token rows; w_*: (e_local, d, f)
    this shard's expert slice; shared: () or replicated shared-expert
    weights, computed between the two a2a hops so the collectives overlap
    independent compute instead of serializing with it.
    """
    from repro.kernels.gmm import ops as gmm_ops
    from repro.models.moe import router_topk

    N, d = x.shape
    e_local = w_gate.shape[0]
    top_k = cfg.num_experts_per_tok

    # 1. route local rows with the full (replicated) router — the same
    #    router_topk the single-device dispatches use (renormalized
    #    top-k, fp32), so routing decisions match bit-for-bit
    weights, indices, _ = router_topk({"router": router_w}, cfg, x)

    # 2. rank each (token, k) pair within its DESTINATION shard — the slot
    #    buffer is per dest shard, not per expert: no dense (E, C) staging
    flat_e = indices.reshape(-1)                   # (N*K,) global expert ids
    dest = flat_e // e_local                       # owning shard per pair
    tok = jnp.repeat(jnp.arange(N), top_k)
    onehot = jax.nn.one_hot(dest, m_shards, dtype=jnp.int32)
    rank = jnp.cumsum(onehot, axis=0) - onehot
    slot = jnp.take_along_axis(rank, dest[:, None], axis=1)[:, 0]
    kept = slot < slots                            # all-True when slots=N*K
    dest_eff = jnp.where(kept, dest, m_shards)     # OOB → scatter drops
    slot = jnp.where(kept, slot, 0)

    # 3. dispatch a2a: token payloads + their LOCAL expert id on the dest
    #    shard (e_local marks an empty slot → pad group after the sort)
    send = jnp.zeros((m_shards, slots, d), x.dtype)
    send = send.at[dest_eff, slot].set(x[tok], mode="drop")
    send_eid = jnp.full((m_shards, slots), e_local, jnp.int32)
    send_eid = send_eid.at[dest_eff, slot].set(
        flat_e % e_local, mode="drop")
    recv = jax.lax.all_to_all(send, model_axis, split_axis=0, concat_axis=0)
    recv_eid = jax.lax.all_to_all(send_eid, model_axis,
                                  split_axis=0, concat_axis=0)

    # 4. shared-expert branch on local rows — no data dependence on the
    #    a2a, so the scheduler hides the dispatch hop under this matmul
    #    (and the combine below is staggered after it)
    shared_out = None
    if shared:  # lint: allow[T101] tuple-or-None closure structure: truthiness is trace-time shape, not data
        sg, su, sd_ = shared
        shared_out = (_act(x @ sg, activation) * (x @ su)) @ sd_

    # 5. per-shard ragged FFN: sort arrivals by local expert id, local
    #    group sizes drive the kernel — empty local experts cost nothing,
    #    pad slots sort into a trailing group no expert owns
    xs = recv.reshape(m_shards * slots, d)
    eid = recv_eid.reshape(-1)
    order = jnp.argsort(eid)                       # stable: preserves (src, slot)
    sizes = jnp.bincount(eid, length=e_local + 1)[:e_local]
    ys = gmm_ops.ragged_moe_ffn(xs[order], w_gate, w_up, w_down, sizes,
                                activation=activation, interpret=interpret)
    real = jnp.arange(m_shards * slots) < jnp.sum(sizes)
    ys = jnp.where(real[:, None], ys, 0).astype(x.dtype)
    back = jnp.zeros_like(ys).at[order].set(ys).reshape(m_shards, slots, d)

    # 6. return a2a, then combine against the top-k weights (fp32 accum)
    ret = jax.lax.all_to_all(back, model_axis, split_axis=0, concat_axis=0)
    gathered = ret[jnp.where(kept, dest, 0), slot]
    wk = weights.reshape(-1) * kept
    out = jnp.zeros((N, d), jnp.float32)
    out = out.at[tok].add(gathered.astype(jnp.float32) * wk[:, None])
    out = out.astype(x.dtype)
    if shared_out is not None:
        out = out + shared_out
    return out


def moe_ep_forward(params: dict, cfg, x: jnp.ndarray, *,
                   mesh=None, layout: Optional[str] = None,
                   capacity_factor: Optional[float] = None,
                   interpret: Optional[bool] = None):
    """(B, T, d) → (B, T, d) expert-parallel MoE FFN.

    Token rows shard over every mesh axis; each shard all-to-alls its
    routed (token, k) payloads to the shards owning the chosen experts,
    which run the ragged gmm over their local expert slice (module
    docstring has the full contract).  ``capacity_factor=None`` (default)
    sizes the per-destination slot buffers to the drop-free worst case
    N_loc·K, making outputs token-identical to the single-device gmm
    dispatch; a finite factor trades a2a volume for possible drops under
    extreme skew.  Needs a mesh with a ``"model"`` axis whose size
    divides E; anything else raises (single-device serving uses the
    ``gmm`` dispatch).
    """
    mesh, layout = resolve_mesh(mesh, layout)
    if mesh is None or "model" not in mesh.axis_names:
        raise ValueError("moe_dispatch='ep' needs a mesh with a 'model' "
                         "axis; single-device serving uses 'gmm'")
    if cfg.num_experts % mesh.shape["model"] != 0:
        raise ValueError(
            f"moe_dispatch='ep': {cfg.num_experts} experts do not divide "
            f"over a model axis of {mesh.shape['model']}")
    interpret = resolve_interpret(interpret)

    B, T, d = x.shape
    m = mesh.shape["model"]
    all_axes = tuple(mesh.axis_names)
    n_shards = math.prod(mesh.shape[a] for a in all_axes)
    K = cfg.num_experts_per_tok

    # row-shard tokens over the whole mesh; pad to an even split (pad rows
    # are zero vectors — routed, computed, sliced off: correctness is
    # unaffected, and no-pad is the common case at serving batch sizes)
    xf = x.reshape(B * T, d)
    n_pad = -(-(B * T) // n_shards) * n_shards
    if n_pad != B * T:
        xf = jnp.pad(xf, ((0, n_pad - B * T), (0, 0)))
    n_local = n_pad // n_shards
    if capacity_factor is None:
        slots = n_local * K                        # drop-free (token-identical)
    else:
        want = -(-int(n_local * K * capacity_factor) // m)
        slots = max(8, min(n_local * K, -(-want // 8) * 8))

    has_shared = "shared" in params
    shared_w = ((params["shared"]["w_gate"], params["shared"]["w_up"],
                 params["shared"]["w_down"]) if has_shared else ())
    in_specs = (P(all_axes, None),                 # tokens: disjoint rows
                P(),                               # router (replicated)
                P("model", None, None), P("model", None, None),
                P("model", None, None),            # expert slices
                (P(), P(), P()) if has_shared else ())
    fn = jax.shard_map(
        partial(_ragged_ep_shard, cfg=cfg, slots=slots,
                activation=cfg.mlp_activation, model_axis="model",
                m_shards=m, interpret=interpret),
        mesh=mesh, in_specs=in_specs, out_specs=P(all_axes, None),
        check_vma=False)
    y = fn(xf, params["router"], params["w_gate"], params["w_up"],
           params["w_down"], shared_w)
    return y[:B * T].reshape(B, T, d)


def ep_a2a_bytes(tokens: int, top_k: int, d_model: int, ep_degree: int,
                 *, dtype_bytes: int = 2) -> float:
    """Per-device all-to-all volume of one EP MoE layer: each routed copy
    crosses the interconnect twice (dispatch + combine), N·K·d·2·bytes
    total, split over ep_degree devices."""
    if ep_degree <= 1:
        return 0.0
    return 2.0 * tokens * top_k * d_model * dtype_bytes / ep_degree


def ep_load_report(params: dict, cfg, tokens, ep_degree: int,
                   *, dtype_bytes: Optional[int] = None) -> Optional[dict]:
    """Host-side expert-load skew probe for serving telemetry (no profiler).

    Routes ``tokens`` through every MoE router via the embedding probe
    (same approximation as ``core/prefetch.router_probe``), folds the (E,)
    activation counts into per-shard loads, and reports the load imbalance
    (max/mean over shards) plus the modeled per-device a2a volume.
    Returns None when there are no tokens or no MoE layers.

    The math runs entirely in numpy on host copies of the embedding and
    router weights (one explicit ``jax.device_get`` per leaf): eager
    device ops here would inject implicit host transfers into every
    guarded warm stream (``transfer_guard``), and telemetry must never
    perturb what it observes.
    """
    import numpy as np

    toks = np.asarray(tokens).reshape(-1)
    if toks.size == 0 or not any(cfg.moe_pattern):
        return None
    table = np.asarray(jax.device_get(params["embed"]["table"]))
    x = table[toks.astype(np.int64)].astype(np.float32)
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    counts = np.zeros((E,), np.float64)
    for i, is_moe in enumerate(cfg.moe_pattern):
        if not is_moe:
            continue
        router = np.asarray(jax.device_get(
            params["layers"][i]["ffn"]["router"])).astype(np.float32)
        logits = np.einsum("nd,pde->pne", x, router)       # (P, n, E)
        # same top-K set as lax.top_k over softmax probs: softmax is
        # monotone, so the K largest logits are the K activated experts
        topk = np.argpartition(-logits, K - 1, axis=-1)[..., :K]
        np.add.at(counts, topk.reshape(-1), 1.0)
    per_shard = counts.reshape(ep_degree, E // ep_degree).sum(-1)
    mean = float(per_shard.mean())
    if dtype_bytes is None:
        dtype_bytes = 4 if cfg.dtype == "float32" else 2
    return {
        "per_shard_load": per_shard.astype(int).tolist(),
        "imbalance": float(per_shard.max() / mean) if mean else 0.0,
        "a2a_bytes_per_device": ep_a2a_bytes(
            int(toks.size), K, cfg.d_model, ep_degree,
            dtype_bytes=dtype_bytes),
    }
