"""Plain float32 reference of the sparse-MoE decoder (Qwen2-MoE, Mixtral),
and of its dense counterpart (Qwen2), whose layers have one FFN.

One layer:

    h = rmsnorm(x) * norm1;  q, k, v = h Wq + bq, h Wk + bk, h Wv + bv
    q, k rotated by RoPE (theta, rotate-half); causal GQA softmax(q k / sqrt(D)) v
    x = x + attn Wo
    h = rmsnorm(x) * norm2;  p = softmax(h R);  top-k of p, renormalised
    x = x + sum_k p_k FFN_{e_k}(h) + FFN_shared(h);  FFN(h) = (silu(h Wg) * h Wu) Wd
    (dense: x = x + FFN(h))

then ``logits = (rmsnorm(x) * final_norm) Head^T``.  Departures of the
served model from Qwen2-MoE that the reference follows, because they are
what the configuration runs: the shared expert has no sigmoid gate, and
the top-k weights are renormalised to sum to 1.

Every matrix product is float32 at ``Precision.HIGHEST``; weights are the
seed's draws (``bench/weights.py``), one layer on the device at a time,
so the reference fits beside nothing else on one chip.  It imports
nothing of the program.

``quant`` ("fp8" or "int8") rounds both inputs of every matrix product
to that format with per-row / per-column absmax scales: the control of a
configuration served in bfloat16.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HI = jax.lax.Precision.HIGHEST


def _quant(x, axis, kind):
    if kind is None:
        return x
    top = {"fp8": 448.0, "int8": 127.0}[kind]
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / top
    if kind == "fp8":
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(a, b, quant):
    """a (..., K) @ b (K, N): a scaled per row, b per column."""
    return jnp.matmul(_quant(a, -1, quant), _quant(b, 0, quant), precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (S, L, H, D) at positions 0..L-1, rotate-half convention."""
    L, D = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv        # (L, D/2)
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _ffn(h, wg, wu, wd, quant):
    return _mm(jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wu, quant), wd, quant)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _layer(p, x, cfg_items, quant):
    cfg = dict(cfg_items)
    S, L, d = x.shape
    H, Hkv = cfg["num_heads"], cfg["num_kv_heads"]
    D = cfg.get("head_dim") or d // H
    eps = cfg.get("norm_eps", 1e-6)
    f32 = lambda a: a.astype(jnp.float32)
    m = p["mixer"]
    h = _rms(x, f32(p["norm1"]["scale"]), eps)
    q, k, v = (_mm(h, f32(m[w]), quant) for w in ("wq", "wk", "wv"))
    if "bq" in m:
        q, k, v = q + f32(m["bq"]), k + f32(m["bk"]), v + f32(m["bv"])
    theta = cfg.get("rope_theta", 10000.0)
    q = _rope(q.reshape(S, L, H, D), theta)
    k = _rope(k.reshape(S, L, Hkv, D), theta)
    v = v.reshape(S, L, Hkv, D)
    qg = _quant(q, -1, quant).reshape(S, L, Hkv, H // Hkv, D)
    s = jnp.einsum("slkgd,smkd->skglm", qg, _quant(k, -1, quant),
                   precision=HI) / np.sqrt(D)
    causal = jnp.tril(jnp.ones((L, L), bool))
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("skglm,smkd->slkgd", _quant(w, -1, quant),
                   _quant(v, -1, quant), precision=HI)
    x = x + _mm(o.reshape(S, L, H * D), f32(m["wo"]), quant)

    ffn = p["ffn"]
    h = _rms(x, f32(p["norm2"]["scale"]), eps).reshape(S * L, d)
    if "router" not in ffn:
        y = _ffn(h, f32(ffn["w_gate"]), f32(ffn["w_up"]), f32(ffn["w_down"]),
                 quant)
        return x + y.reshape(S, L, d)
    probs = jax.nn.softmax(_mm(h, ffn["router"], quant), axis=-1)
    top, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    top = top / jnp.sum(top, -1, keepdims=True)
    combine = jnp.sum(jax.nn.one_hot(idx, cfg["num_experts"]) * top[..., None],
                      axis=1)                                   # (N, E)

    def expert(acc, e):
        wg, wu, wd, c = e
        return acc + _ffn(h, f32(wg), f32(wu), f32(wd), quant) * c[:, None], None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (ffn["w_gate"], ffn["w_up"], ffn["w_down"], combine.T))
    if "shared" in ffn:
        sh = ffn["shared"]
        y = y + _ffn(h, f32(sh["w_gate"]), f32(sh["w_up"]), f32(sh["w_down"]),
                     quant)
    return x + y.reshape(S, L, d)


def final_hidden(cfg: dict, seed: int, tokens: np.ndarray,
                 quant: Optional[str] = None):
    """Final-norm hidden states (S, L, d) float32 of causal forwards over
    ``tokens`` (S, L), every position from 0."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str, bool))))
    g = weights.global_params(cfg, seed)
    x = jnp.asarray(g["embed"]["table"]).astype(jnp.float32)[jnp.asarray(tokens)]
    for layer in range(cfg["num_layers"]):
        p = weights.layer_params(cfg, seed, layer)
        x = _layer(p, x, items, quant)
        x.block_until_ready()
        del p
    eps = cfg.get("norm_eps", 1e-6)
    return _rms(x, g["final_norm"]["scale"].astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("quant",))
def _readings(h, table, tok, quant):
    logits = _mm(h, table.astype(jnp.float32).T, quant)
    at = jnp.take_along_axis(logits, tok[:, None], axis=1)[:, 0]
    return jnp.max(logits, -1), at, jnp.argmax(logits, -1).astype(jnp.int32)


def logit_readings(cfg: dict, seed: int, h_rows, tokens, *,
                   quant: Optional[str] = None, block: int = 512):
    """Per row of ``h_rows`` (N, d): the best logit, the logit of
    ``tokens`` (N,), and the argmax, from the output head in row blocks."""
    g = weights.global_params(cfg, seed)
    table = g["head" if "head" in g else "embed"]["table"]
    n = h_rows.shape[0]
    pad = -n % block
    h = jnp.pad(jnp.asarray(h_rows, jnp.float32), ((0, pad), (0, 0)))
    t = jnp.pad(jnp.asarray(tokens, jnp.int32), (0, pad))
    outs = [jax.device_get(_readings(h[a:a + block], table, t[a:a + block],
                                     quant))
            for a in range(0, n + pad, block)]
    best, at, arg = (np.concatenate(z)[:n] for z in zip(*outs))
    return best, at, arg
