"""Core layers: norms, gated MLPs, embeddings, rotary embeddings (+M-RoPE).

Everything is functional: ``init_*(key, cfg) -> params`` and
``apply(params, x) -> y`` with params as plain dict pytrees.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------
# Jitted per leaf so the fp32 draw, the scale and the cast fuse into one
# program that writes only the final dtype: at published widths an eager
# draw would first materialize every expert tensor in fp32 (4.7 GB for
# qwen2-57b-a14b's two-layer (2, 64, 3584, 2560) stack).  The draws are
# bit-identical to the eager ones.

@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def dense_init(key, shape, dtype, scale: float = 1.0):
    """Truncated-normal fan-in init (matches common LLM practice)."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -3, 3, shape, jnp.float32) * std).astype(dtype)


@functools.partial(jax.jit, static_argnums=(1, 2))
def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(cfg, dtype) -> dict:
    p = {"scale": jnp.ones((cfg.d_model,), dtype)}
    if cfg.norm_type == "layernorm":
        p["bias"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def apply_norm(params: dict, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if "bias" in params:  # layernorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps)
        out = out * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + eps) * params["scale"].astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# gated MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (d_model, d_ff), dtype),
        "w_up": dense_init(k2, (d_model, d_ff), dtype),
        "w_down": dense_init(k3, (d_ff, d_model), dtype),
    }


def apply_mlp(params: dict, x: jnp.ndarray, activation: str = "silu") -> jnp.ndarray:
    gate = x @ params["w_gate"]
    up = x @ params["w_up"]
    if activation == "gelu":
        act = jax.nn.gelu(gate, approximate=True)
    else:
        act = jax.nn.silu(gate)
    return (act * up) @ params["w_down"]


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    """Inverse frequencies, shape (head_dim // 2,)."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., T, H, D); positions: broadcastable to (..., T)."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta)                              # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * inv    # (..., T, D/2)
    sin = jnp.sin(ang)[..., None, :]                        # (..., T, 1, D/2)
    cos = jnp.cos(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jnp.ndarray,
    positions: jnp.ndarray,
    theta: float,
    sections: Tuple[int, ...],
) -> jnp.ndarray:
    """Multimodal RoPE (Qwen2-VL, arXiv:2409.12191).

    ``positions``: (..., T, 3) — (temporal, height, width) position ids.
    ``sections``: rotary half-dims assigned to each component; must sum to
    head_dim // 2.  For pure text all three components carry the same id, and
    M-RoPE degenerates to 1-D RoPE exactly.
    """
    d = x.shape[-1]
    assert sum(sections) == d // 2, (sections, d)
    inv = rope_freqs(d, theta)                              # (D/2,)
    # choose which position component drives each frequency band
    comp = jnp.concatenate(
        [jnp.full((s,), i, dtype=jnp.int32) for i, s in enumerate(sections)]
    )                                                        # (D/2,)
    pos = jnp.take_along_axis(
        positions.astype(jnp.float32),
        jnp.broadcast_to(comp, positions.shape[:-1] + (d // 2,)).astype(jnp.int32),
        axis=-1,
    )                                                        # (..., T, D/2)
    ang = pos * inv
    sin = jnp.sin(ang)[..., None, :]
    cos = jnp.cos(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(num_pos: int, d_model: int) -> jnp.ndarray:
    """Standard sinusoidal table (whisper encoder)."""
    pos = jnp.arange(num_pos, dtype=jnp.float32)[:, None]
    i = jnp.arange(d_model // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10_000.0, 2 * i / d_model)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# embedding / unembedding
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d_model: int, dtype) -> dict:
    return {"table": embed_init(key, (vocab, d_model), dtype)}


def embed(params: dict, tokens: jnp.ndarray, scale: bool = False) -> jnp.ndarray:
    x = params["table"][tokens]
    if scale:  # gemma-style sqrt(d) scaling
        x = x * jnp.asarray(math.sqrt(x.shape[-1]), x.dtype)
    return x


def unembed(params: dict, x: jnp.ndarray, softcap: float = 0.0) -> jnp.ndarray:
    logits = x @ params["table"].T
    if softcap > 0:
        logits = jnp.tanh(logits / softcap) * softcap
    return logits


def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    return jnp.tanh(x / cap) * cap if cap > 0 else x
