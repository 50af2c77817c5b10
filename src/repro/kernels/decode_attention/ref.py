"""Pure-jnp oracle for decode/verify attention."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def decode_attention_ref(
    q: jnp.ndarray,            # (B, Hq, T, D)
    k: jnp.ndarray,            # (B, Hkv, S, D)
    v: jnp.ndarray,
    lengths: jnp.ndarray,      # (B,)
    *,
    scale: float = 0.0,
    logit_cap: float = 0.0,
) -> jnp.ndarray:
    B, Hq, T, D = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    g = Hq // Hkv
    if scale == 0.0:
        scale = 1.0 / math.sqrt(D)
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if logit_cap > 0:
        s = jnp.tanh(s / logit_cap) * logit_cap
    q_pos = lengths[:, None, None, None] + jnp.arange(T)[None, None, :, None]
    k_pos = jnp.arange(S)[None, None, None, :]
    s = jnp.where(k_pos <= q_pos, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhts,bhsd->bhtd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_decode_attention_ref(
    q: jnp.ndarray,            # (B, Hq, T, D)
    k_pages: jnp.ndarray,      # (NP, Hkv, ps, D) head-major page pool
    v_pages: jnp.ndarray,
    lengths: jnp.ndarray,      # (B,)
    table: jnp.ndarray,        # (B, MP) logical page -> physical page
    *,
    scale: float = 0.0,
    logit_cap: float = 0.0,
) -> jnp.ndarray:
    """Oracle for the paged kernel: gather ``pool[table]`` into the dense
    (B, Hkv, MP*ps, D) view, then delegate to :func:`decode_attention_ref`.
    Logical positions beyond ``length + T - 1`` are masked there, so trash
    or stale page contents never reach the softmax."""
    B, MP = table.shape
    _, Hkv, ps, D = k_pages.shape

    def view(pool):
        g = jnp.asarray(pool)[jnp.asarray(table)]           # (B, MP, Hkv, ps, D)
        return g.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, MP * ps, D)

    return decode_attention_ref(
        q, view(k_pages), view(v_pages), lengths,
        scale=scale, logit_cap=logit_cap)
