"""Public jit wrapper: (B, T, H, D) layout used by the model code."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.flash_attention.flash_attention import flash_attention_bhtd


def flash_attention(
    q: jnp.ndarray,            # (B, T, Hq, D)
    k: jnp.ndarray,            # (B, S, Hkv, D)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    scale: float = 0.0,
    logit_cap: float = 0.0,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    out = flash_attention_bhtd(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=causal, window=window, scale=scale, logit_cap=logit_cap,
        interpret=resolve_interpret(interpret))
    return out.transpose(0, 2, 1, 3)
