"""Attention blocks: GQA/MQA, sliding-window (ring cache), MLA, cross-attn.

Three execution modes share one code path:
  * ``train``   — full causal self-attention over (B, T), no cache.
  * ``prefill`` — causal over the prompt, writes the KV cache from pos 0.
  * ``extend``  — T new tokens (T=1 → plain decode, T=γ+1 → SD verify)
                  appended at per-sequence offsets ``lengths`` against a
                  populated cache.

Caches:
  full attention   {"k": (B, S, Hkv, D), "v": (B, S, Hkv, D)}
  paged            {"k_pages": (NP, Hkv, page, D), "v_pages": ...} head-major
                   pool; MLA {"latent_pages": (NP, page, r_kv), ...}
  sliding window   {"k": (B, W, Hkv, D), "v": ..., "pos": (B, W) int32}
                   ring buffer, slot = position % W, ``pos`` init −1
  MLA              {"latent": (B, S, r_kv), "k_rope": (B, S, Dr)}
  cross            {"k": (B, S_enc, Hkv, D), "v": ...} — static after prefill

RoPE is applied at write time for K (absolute positions), query side at read
time, so cached K never needs re-rotation.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import apply_mrope, apply_rope, dense_init, softcap

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_gqa(key, cfg, dtype) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, cfg.num_heads * hd), dtype),
        "wk": dense_init(ks[1], (d, cfg.num_kv_heads * hd), dtype),
        "wv": dense_init(ks[2], (d, cfg.num_kv_heads * hd), dtype),
        "wo": dense_init(ks[3], (cfg.num_heads * hd, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.num_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
    return p


def init_mla(key, cfg, dtype) -> dict:
    d = cfg.d_model
    r_kv, r_q = cfg.mla_kv_lora_rank, cfg.mla_q_lora_rank
    dn, dr, dv = cfg.mla_qk_nope_dim, cfg.mla_qk_rope_dim, cfg.mla_v_head_dim
    H = cfg.num_heads
    ks = jax.random.split(key, 8)
    p = {
        "w_dkv": dense_init(ks[0], (d, r_kv + dr), dtype),         # latent + k_rope
        "w_uk": dense_init(ks[1], (r_kv, H * dn), dtype),
        "w_uv": dense_init(ks[2], (r_kv, H * dv), dtype),
        "wo": dense_init(ks[3], (H * dv, d), dtype),
        "kv_norm": jnp.ones((r_kv,), dtype),
    }
    if r_q > 0:
        p["w_dq"] = dense_init(ks[4], (d, r_q), dtype)
        p["w_uq"] = dense_init(ks[5], (r_q, H * (dn + dr)), dtype)
        p["q_norm"] = jnp.ones((r_q,), dtype)
    else:
        p["wq"] = dense_init(ks[6], (d, H * (dn + dr)), dtype)
    return p


def init_cross_attn(key, cfg, dtype) -> dict:
    return init_gqa(key, cfg, dtype)


# ---------------------------------------------------------------------------
# cache constructors
# ---------------------------------------------------------------------------

# Extra ring slots so a batched extend of T ≤ SWA_RING_PAD+1 tokens never
# evicts an entry still inside an earlier query's window (SD verify writes
# gamma+1 tokens before any of them attends).
SWA_RING_PAD = 8


def make_attn_cache(cfg, batch: int, max_seq: int, kind: str, dtype, *,
                    paged: bool = False, page_size: int = 64,
                    pool_pages: Optional[int] = None) -> dict:
    """Per-layer decode cache.  ``paged=True`` stores full-attn / MLA
    sequence axes as a shared physical page pool (``*_pages`` leaves)
    addressed through the model-level block table: K/V pools are
    head-major (pool_pages, Hkv, page_size, D) so one (page, head) is the
    contiguous tile the paged kernel reads, MLA pools (pool_pages,
    page_size, dim).  SWA rings are already bounded per row and stay
    dense."""
    hd = cfg.head_dim
    if kind == "swa":
        w = min(cfg.sliding_window + SWA_RING_PAD, max_seq)
        return {
            "k": jnp.zeros((batch, w, cfg.num_kv_heads, hd), dtype),
            "v": jnp.zeros((batch, w, cfg.num_kv_heads, hd), dtype),
            "pos": jnp.full((batch, w), -1, jnp.int32),
        }
    if paged:
        npg = (batch * (-(-max_seq // page_size)) + 1
               if pool_pages is None else pool_pages)
        if kind == "mla":
            return {
                "latent_pages": jnp.zeros(
                    (npg, page_size, cfg.mla_kv_lora_rank), dtype),
                "k_rope_pages": jnp.zeros(
                    (npg, page_size, cfg.mla_qk_rope_dim), dtype),
            }
        return {
            "k_pages": jnp.zeros((npg, cfg.num_kv_heads, page_size, hd),
                                 dtype),
            "v_pages": jnp.zeros((npg, cfg.num_kv_heads, page_size, hd),
                                 dtype),
        }
    if kind == "mla":
        return {
            "latent": jnp.zeros((batch, max_seq, cfg.mla_kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, max_seq, cfg.mla_qk_rope_dim), dtype),
        }
    return {
        "k": jnp.zeros((batch, max_seq, cfg.num_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, max_seq, cfg.num_kv_heads, hd), dtype),
    }


def _paged_write(pool: jnp.ndarray, table: jnp.ndarray,
                 positions: jnp.ndarray, vals: jnp.ndarray) -> jnp.ndarray:
    """Scatter per-row values ``vals`` (B, T, ...) at logical ``positions``
    (B, T) into the physical page pool through the block table (B, MP).
    Every pool keeps its page-slot axis second to last: (NP, page, dim)
    for MLA, head-major (NP, H, page, D) for K/V.  Unallocated positions
    resolve to the trash page — harmless."""
    ps = pool.shape[-2]
    bidx = jnp.arange(positions.shape[0])[:, None]
    pid = table[bidx, positions // ps]                       # (B, T)
    # the (B, T) index dims lead the indexed view whether or not a head
    # axis sits between them, so it is (B, T, ...) like ``vals``
    heads = (slice(None),) * (pool.ndim - 3)
    return pool.at[(pid, *heads, positions % ps)].set(vals)


def _paged_view(pool: jnp.ndarray, table: jnp.ndarray):
    """Gather the (B, MP*page, ...) dense view of a paged pool plus its
    logical key positions (a head-major K/V pool yields (B, MP*page, H, D)
    like a dense cache).  Stale/trash content is masked the same way
    rejected SD suffixes are: the causal mask only admits positions the
    row has actually written (k_pos <= q_pos)."""
    B, MP = table.shape
    ps = pool.shape[-2]
    view = jnp.moveaxis(pool[table], -2, 2)                # (B, MP, ps, ...)
    view = view.reshape((B, MP * ps) + view.shape[3:])
    k_pos = jnp.broadcast_to(jnp.arange(MP * ps)[None, :], (B, MP * ps))
    return view, k_pos


# ---------------------------------------------------------------------------
# core scaled-dot-product with GQA grouping
# ---------------------------------------------------------------------------

def _sdpa(q, k, v, mask, scale, logit_cap: float = 0.0):
    """q: (B,T,Hq,D)  k/v: (B,S,Hkv,D)  mask: (B,1,T,S) bool → (B,T,Hq,Dv)."""
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    g = Hq // Hkv
    qg = q.reshape(B, T, Hkv, g, D)
    logits = jnp.einsum("btkgd,bskd->bkgts", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if logit_cap > 0:
        logits = softcap(logits, logit_cap)
    logits = jnp.where(mask[:, :, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v.astype(jnp.float32))
    return out.reshape(B, T, Hq, v.shape[-1]).astype(q.dtype)


def _chunk_inputs(k, v, k_pos, chunk):
    B, S, Hkv, D = k.shape
    Dv = v.shape[-1]
    pad = (-S) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
    n = (S + pad) // chunk
    kc = k.reshape(B, n, chunk, Hkv, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n, chunk, Hkv, Dv).transpose(1, 0, 2, 3, 4)
    pc = k_pos.reshape(B, n, chunk).transpose(1, 0, 2)
    return kc, vc, pc, pad


def _chunk_scores(qg, k_t, p_t, q_pos, scale, logit_cap, causal, window):
    """(B,Hkv,g,T,C) softcapped+masked scores for one KV chunk (f32)."""
    B, T = q_pos.shape
    C = p_t.shape[-1]
    s = jnp.einsum("btkgd,bckd->bkgtc", qg, k_t.astype(jnp.float32)) * scale
    if logit_cap > 0:
        s = softcap(s, logit_cap)
    valid = p_t[:, None, :] >= 0
    if causal:
        valid &= p_t[:, None, :] <= q_pos[:, :, None]
        if window > 0:
            valid &= p_t[:, None, :] > q_pos[:, :, None] - window
    else:
        valid = jnp.broadcast_to(valid, (B, T, C))
    return jnp.where(valid[:, None, None, :, :], s, NEG_INF)


def _chunked_fwd(q, k, v, q_pos, k_pos, scale, window, logit_cap, chunk, causal):
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    Dv = v.shape[-1]
    g = Hq // Hkv
    kc, vc, pc, _ = _chunk_inputs(k, v, k_pos, chunk)
    qg = q.reshape(B, T, Hkv, g, D).astype(jnp.float32)

    def body(carry, inputs):
        m, l, acc = carry                       # (B,Hkv,g,T), ..., (...,Dv)
        k_t, v_t, p_t = inputs
        s = _chunk_scores(qg, k_t, p_t, q_pos, scale, logit_cap, causal, window)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgtc,bckd->bkgtd", p, v_t.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hkv, g, T), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, g, T), jnp.float32)
    a0 = jnp.zeros((B, Hkv, g, T, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, T, Hq, Dv).astype(q.dtype)
    return out, (m, l)


@functools.lru_cache(maxsize=None)
def _make_chunked(scale, window, logit_cap, chunk, causal):
    """Flash-attention with a recompute backward (custom_vjp): neither pass
    materializes (T, S) scores, and — unlike autodiff through the forward
    scan — the backward saves only O(T + S) residuals (out, m, l), not
    per-chunk carries.  This is what makes 32k-token training lower with
    sane memory (EXPERIMENTS.md §Dry-run)."""

    @jax.custom_vjp
    def f(q, k, v, q_pos, k_pos):
        return _chunked_fwd(q, k, v, q_pos, k_pos, scale, window, logit_cap,
                            chunk, causal)[0]

    def fwd(q, k, v, q_pos, k_pos):
        out, (m, l) = _chunked_fwd(q, k, v, q_pos, k_pos, scale, window,
                                   logit_cap, chunk, causal)
        return out, (q, k, v, q_pos, k_pos, out, m, l)

    def bwd(res, dout):
        q, k, v, q_pos, k_pos, out, m, l = res
        B, T, Hq, D = q.shape
        S, Hkv = k.shape[1], k.shape[2]
        Dv = v.shape[-1]
        g = Hq // Hkv
        kc, vc, pc, pad = _chunk_inputs(k, v, k_pos, chunk)
        qg = q.reshape(B, T, Hkv, g, D).astype(jnp.float32)
        do = dout.reshape(B, T, Hkv, g, Dv).transpose(0, 2, 3, 1, 4).astype(jnp.float32)
        og = out.reshape(B, T, Hkv, g, Dv).transpose(0, 2, 3, 1, 4).astype(jnp.float32)
        l_safe = jnp.maximum(l, 1e-30)
        Drow = jnp.sum(do * og, axis=-1)                    # (B,Hkv,g,T)

        def body(dq_acc, inputs):
            k_t, v_t, p_t = inputs
            s = _chunk_scores(qg, k_t, p_t, q_pos, scale, logit_cap, causal,
                              window)
            p = jnp.exp(s - m[..., None]) / l_safe[..., None]
            dp = jnp.einsum("bkgtd,bckd->bkgtc", do, v_t.astype(jnp.float32))
            ds = p * (dp - Drow[..., None])
            if logit_cap > 0:
                ds = ds * (1.0 - jnp.square(jnp.tanh(
                    jnp.einsum("btkgd,bckd->bkgtc", qg,
                               k_t.astype(jnp.float32)) * scale / logit_cap)))
            dq_acc = dq_acc + jnp.einsum("bkgtc,bckd->btkgd", ds,
                                         k_t.astype(jnp.float32)) * scale
            dk_t = jnp.einsum("bkgtc,btkgd->bckd", ds, qg) * scale
            dv_t = jnp.einsum("bkgtc,bkgtd->bckd", p, do)
            return dq_acc, (dk_t, dv_t)

        dq0 = jnp.zeros((B, T, Hkv, g, D), jnp.float32)
        dq, (dkc, dvc) = jax.lax.scan(body, dq0, (kc, vc, pc))
        dq = dq.reshape(B, T, Hq, D).astype(q.dtype)
        dk = dkc.transpose(1, 0, 2, 3, 4).reshape(B, S + pad, Hkv, D)
        dv = dvc.transpose(1, 0, 2, 3, 4).reshape(B, S + pad, Hkv, Dv)
        dk = dk[:, :S].astype(k.dtype)
        dv = dv[:, :S].astype(v.dtype)
        import numpy as _np
        zq = _np.zeros(q_pos.shape, jax.dtypes.float0)
        zk = _np.zeros(k_pos.shape, jax.dtypes.float0)
        return dq, dk, dv, zq, zk

    f.defvjp(fwd, bwd)
    return f


def chunked_sdpa(
    q, k, v, q_pos, k_pos, *,
    scale: float,
    window: int = 0,
    logit_cap: float = 0.0,
    chunk: int = 1024,
    causal: bool = True,
):
    """Online-softmax attention, ``lax.scan`` over key chunks, flash-style
    recompute backward.  Never materializes the (T, S) score matrix in
    either pass.  q: (B,T,Hq,D), k/v: (B,S,Hkv,D), q_pos: (B,T),
    k_pos: (B,S).  Invalid slots carry k_pos < 0."""
    fn = _make_chunked(float(scale), int(window), float(logit_cap),
                       int(chunk), bool(causal))
    return fn(q, k, v, q_pos, k_pos)


def _causal_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray, window: int = 0):
    """q_pos: (B,T), k_pos: (B,S) → (B,1,T,S).  k visible iff k_pos <= q_pos
    (and within the window when window > 0) and k_pos >= 0 (valid slot)."""
    m = (k_pos[:, None, :] <= q_pos[:, :, None]) & (k_pos[:, None, :] >= 0)
    if window > 0:
        m &= k_pos[:, None, :] > q_pos[:, :, None] - window
    return m[:, None, :, :]


# ---------------------------------------------------------------------------
# GQA / SWA forward
# ---------------------------------------------------------------------------

def _project_qkv(params, cfg, x):
    hd = cfg.head_dim
    B, T, _ = x.shape
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(B, T, cfg.num_heads, hd)
    k = k.reshape(B, T, cfg.num_kv_heads, hd)
    v = v.reshape(B, T, cfg.num_kv_heads, hd)
    return q, k, v


def _rotate(cfg, q, k, positions, mrope_positions=None):
    if cfg.rope_type == "mrope":
        if mrope_positions is None:  # text-only: all three components equal
            mrope_positions = jnp.repeat(positions[..., None], 3, axis=-1)
        q = apply_mrope(q, mrope_positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, mrope_positions, cfg.rope_theta, cfg.mrope_sections)
    elif cfg.rope_type == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    # "learned"/"sinusoidal": positions added at the embedding level
    return q, k


def gqa_forward(
    params: dict,
    cfg,
    x: jnp.ndarray,                  # (B, T, d)
    positions: jnp.ndarray,          # (B, T) absolute positions
    *,
    kind: str = "attn",              # "attn" | "swa"
    cache: Optional[dict] = None,
    mode: str = "train",             # train | prefill | extend
    mrope_positions=None,
    use_flash: bool = False,
    causal: bool = True,
    page_table: Optional[jnp.ndarray] = None,
    paged_attention: str = "kernel",
    mesh=None,
) -> Tuple[jnp.ndarray, Optional[dict]]:
    B, T, _ = x.shape
    window = cfg.sliding_window if kind == "swa" else 0
    scale = 1.0 / math.sqrt(cfg.head_dim)
    q, k, v = _project_qkv(params, cfg, x)
    q, k = _rotate(cfg, q, k, positions, mrope_positions)
    cap = cfg.attn_logit_softcap

    def attend(q_, k_, v_, q_pos, k_pos):
        """Backend selection: Pallas flash (train/prefill, TPU target),
        chunked online-softmax (long sequences), naive masked SDPA."""
        S = k_.shape[1]
        if use_flash and causal and T == S and T >= 128:
            from repro.kernels.flash_attention import ops as flash_ops
            return flash_ops.flash_attention(
                q_, k_, v_, causal=True, window=window, scale=scale,
                logit_cap=cap)
        if T * S > 2_097_152:  # avoid materializing big (T,S) score tensors
            return chunked_sdpa(q_, k_, v_, q_pos, k_pos, scale=scale,
                                window=window, logit_cap=cap, causal=causal)
        mask = _causal_mask(q_pos, k_pos, window) if causal else (
            (k_pos[:, None, :] >= 0)[:, None, :, :]
            & jnp.ones((B, 1, T, k_pos.shape[-1]), bool))
        return _sdpa(q_, k_, v_, mask, scale, cap)

    if mode in ("train", "prefill"):
        # attention over the in-flight K/V (never through the cache: avoids
        # ring-slot collisions for SWA and S_max-sized score tensors)
        out = attend(q, k, v, positions, positions)
        if mode == "prefill" and cache is not None:
            if kind == "swa":
                w = cache["k"].shape[1]
                tw = min(T, w)
                slots = positions[:, -tw:] % w
                bidx = jnp.arange(B)[:, None]
                cache = {
                    "k": cache["k"].at[bidx, slots].set(k[:, -tw:]),
                    "v": cache["v"].at[bidx, slots].set(v[:, -tw:]),
                    "pos": cache["pos"].at[bidx, slots].set(positions[:, -tw:]),
                }
            elif "k_pages" in cache:
                cache = {
                    "k_pages": _paged_write(cache["k_pages"], page_table,
                                            positions, k),
                    "v_pages": _paged_write(cache["v_pages"], page_table,
                                            positions, v),
                }
            else:
                bidx = jnp.arange(B)[:, None]
                cache = {
                    "k": cache["k"].at[bidx, positions].set(k),
                    "v": cache["v"].at[bidx, positions].set(v),
                }
        return out.reshape(B, T, -1) @ params["wo"], cache

    # mode == "extend": T new tokens against the populated cache
    bidx = jnp.arange(B)[:, None]
    if kind == "swa":
        w = cache["k"].shape[1]
        slots = positions % w
        cache = {
            "k": cache["k"].at[bidx, slots].set(k),
            "v": cache["v"].at[bidx, slots].set(v),
            "pos": cache["pos"].at[bidx, slots].set(positions),
        }
        k_pos = cache["pos"]
        out = attend(q, cache["k"], cache["v"], positions, k_pos)
    elif "k_pages" in cache:
        # paged: write the new tokens through the block table, then attend.
        # Decode/verify widths (T <= 8) take the block-table-walking Pallas
        # kernel — KV pages stream straight from the pool, no dense gather;
        # wider tail-prefill extends (chunked admission) and the explicit
        # paged_attention="gather" fallback materialize the dense view.
        cache = {
            "k_pages": _paged_write(cache["k_pages"], page_table, positions,
                                    k),
            "v_pages": _paged_write(cache["v_pages"], page_table, positions,
                                    v),
        }
        if paged_attention == "kernel" and causal and T <= 8:
            from repro.kernels.decode_attention import ops as dec_ops
            out = dec_ops.paged_decode_attention(
                q, cache["k_pages"], cache["v_pages"], positions[:, 0],
                page_table, scale=scale, logit_cap=cap, mesh=mesh)
        else:
            k_view, k_pos = _paged_view(cache["k_pages"], page_table)
            v_view, _ = _paged_view(cache["v_pages"], page_table)
            out = attend(q, k_view, v_view, positions, k_pos)
    else:
        cache = {
            "k": cache["k"].at[bidx, positions].set(k),
            "v": cache["v"].at[bidx, positions].set(v),
        }
        S = cache["k"].shape[1]
        if use_flash and window == 0 and cap == 0.0 and S >= 512:
            # Pallas decode/verify kernel: gamma+1 queries vs the long KV
            # cache, per-sequence lengths = first query position
            from repro.kernels.decode_attention import ops as dec_ops
            out = dec_ops.decode_attention(
                q, cache["k"], cache["v"], positions[:, 0], scale=scale)
        else:
            k_pos = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
            out = attend(q, cache["k"], cache["v"], positions, k_pos)
    return out.reshape(B, T, -1) @ params["wo"], cache


# ---------------------------------------------------------------------------
# MLA forward (DeepSeek-V2 / MiniCPM3 multi-head latent attention)
# ---------------------------------------------------------------------------

def _mla_q(params, cfg, x):
    B, T, _ = x.shape
    H = cfg.num_heads
    dn, dr = cfg.mla_qk_nope_dim, cfg.mla_qk_rope_dim
    if "w_dq" in params:
        ql = x @ params["w_dq"]
        ql = _rms(ql, params["q_norm"], cfg.norm_eps)
        q = ql @ params["w_uq"]
    else:
        q = x @ params["wq"]
    q = q.reshape(B, T, H, dn + dr)
    return q[..., :dn], q[..., dn:]


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def mla_forward(
    params: dict,
    cfg,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    *,
    cache: Optional[dict] = None,
    mode: str = "train",
    page_table: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Optional[dict]]:
    B, T, _ = x.shape
    H = cfg.num_heads
    dn, dr, dv = cfg.mla_qk_nope_dim, cfg.mla_qk_rope_dim, cfg.mla_v_head_dim
    r_kv = cfg.mla_kv_lora_rank
    scale = 1.0 / math.sqrt(dn + dr)

    q_nope, q_rope = _mla_q(params, cfg, x)                     # (B,T,H,dn/(dr))
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    dkv = x @ params["w_dkv"]                                   # (B,T,r_kv+dr)
    latent = _rms(dkv[..., :r_kv], params["kv_norm"], cfg.norm_eps)
    k_rope_new = apply_rope(
        dkv[..., None, r_kv:], positions, cfg.rope_theta
    )[..., 0, :]                                                # (B,T,dr) single shared head

    if mode in ("train", "prefill") or cache is None:
        lat_all, k_rope_all = latent, k_rope_new
        k_pos = positions
        new_cache = None
        if mode == "prefill" and cache is not None:
            if "latent_pages" in cache:
                new_cache = {
                    "latent_pages": _paged_write(cache["latent_pages"],
                                                 page_table, positions,
                                                 latent),
                    "k_rope_pages": _paged_write(cache["k_rope_pages"],
                                                 page_table, positions,
                                                 k_rope_new),
                }
            else:
                bidx = jnp.arange(B)[:, None]
                new_cache = {
                    "latent": cache["latent"].at[bidx, positions].set(latent),
                    "k_rope": cache["k_rope"].at[bidx, positions].set(
                        k_rope_new),
                }
    elif "latent_pages" in cache:
        new_cache = {
            "latent_pages": _paged_write(cache["latent_pages"], page_table,
                                         positions, latent),
            "k_rope_pages": _paged_write(cache["k_rope_pages"], page_table,
                                         positions, k_rope_new),
        }
        lat_all, k_pos = _paged_view(new_cache["latent_pages"], page_table)
        k_rope_all, _ = _paged_view(new_cache["k_rope_pages"], page_table)
    else:
        bidx = jnp.arange(B)[:, None]
        lat_all = cache["latent"].at[bidx, positions].set(latent)
        k_rope_all = cache["k_rope"].at[bidx, positions].set(k_rope_new)
        new_cache = {"latent": lat_all, "k_rope": k_rope_all}
        S = lat_all.shape[1]
        k_pos = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

    # expand latent → per-head K_nope and V; fold the shared rope-K into a
    # single concatenated head dim so standard SDPA applies:
    #   q·k = q_nope·k_nope + q_rope·k_rope
    S = lat_all.shape[1]
    k_nope = (lat_all @ params["w_uk"]).reshape(B, S, H, dn)
    v = (lat_all @ params["w_uv"]).reshape(B, S, H, dv)
    k_cat = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope_all[:, :, None, :], (B, S, H, dr))], axis=-1)
    q_cat = jnp.concatenate([q_nope, q_rope], axis=-1)

    if T * S > 2_097_152:
        out = chunked_sdpa(q_cat, k_cat, v, positions, k_pos, scale=scale)
    else:
        mask = _causal_mask(positions, k_pos, 0)
        out = _sdpa(q_cat, k_cat, v, mask, scale)
    out = out.reshape(B, T, H * dv)
    return out @ params["wo"], new_cache


# ---------------------------------------------------------------------------
# cross attention (whisper decoder → encoder output)
# ---------------------------------------------------------------------------

def cross_attn_prefill_cache(params: dict, cfg, enc_out: jnp.ndarray, dtype) -> dict:
    """Project encoder output to K/V once; static for the whole decode."""
    B, S, _ = enc_out.shape
    k = (enc_out @ params["wk"]).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = (enc_out @ params["wv"]).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    return {"k": k.astype(dtype), "v": v.astype(dtype)}


def cross_attn_forward(params: dict, cfg, x: jnp.ndarray, kv: dict) -> jnp.ndarray:
    B, T, _ = x.shape
    q = (x @ params["wq"]).reshape(B, T, cfg.num_heads, cfg.head_dim)
    S = kv["k"].shape[1]
    scale = 1.0 / math.sqrt(cfg.head_dim)
    if T * S > 2_097_152:  # chunked online softmax for long decoder sequences
        q_pos = jnp.zeros((B, T), jnp.int32)
        k_pos = jnp.zeros((B, S), jnp.int32)
        out = chunked_sdpa(q, kv["k"], kv["v"], q_pos, k_pos, scale=scale,
                           causal=False, chunk=min(1024, S))
    else:
        mask = jnp.ones((B, 1, T, S), bool)
        out = _sdpa(q, kv["k"], kv["v"], mask, scale)
    return out.reshape(B, T, -1) @ params["wo"]
