"""Public jit wrapper, (B, T, H, D) layout."""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import resolve_interpret
from repro.kernels.decode_attention.decode_attention import (
    decode_attention_bhtd, paged_decode_attention_bhtd)


def decode_attention(
    q: jnp.ndarray,            # (B, T, Hq, D)
    k: jnp.ndarray,            # (B, S, Hkv, D)
    v: jnp.ndarray,
    lengths: jnp.ndarray,      # (B,)
    *,
    scale: float = 0.0,
    logit_cap: float = 0.0,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Dense decode/verify attention over a contiguous (B, S) KV cache:
    the online-softmax Pallas kernel behind the non-paged extend path.
    Queries sit at absolute positions ``lengths + t`` (causal)."""
    out = decode_attention_bhtd(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        lengths, scale=scale, logit_cap=logit_cap,
        interpret=resolve_interpret(interpret))
    return out.transpose(0, 2, 1, 3)


def _paged_btqd(q, k_pages, v_pages, lengths, table, *, scale, logit_cap,
                interpret):
    out = paged_decode_attention_bhtd(
        q.transpose(0, 2, 1, 3), k_pages, v_pages, lengths, table,
        scale=scale, logit_cap=logit_cap, interpret=interpret)
    return out.transpose(0, 2, 1, 3)


def paged_decode_attention(
    q: jnp.ndarray,            # (B, T, Hq, D)
    k_pages: jnp.ndarray,      # (NP, Hkv, ps, D) head-major page pool
    v_pages: jnp.ndarray,
    lengths: jnp.ndarray,      # (B,)
    table: jnp.ndarray,        # (B, MP) logical page -> physical page
    *,
    scale: float = 0.0,
    logit_cap: float = 0.0,
    interpret: Optional[bool] = None,
    mesh=None,
) -> jnp.ndarray:
    """Block-table-walking decode/verify attention over the paged KV pool.

    Reads K/V pages directly from the pool via scalar-prefetched page
    indices — no ``pool[table]`` dense gather — and returns (B, T, Hq, D)
    matching :func:`decode_attention` on the gathered view exactly (same
    masking contract; positions past ``length + t`` never contribute).

    With a ``mesh`` the kernel runs per shard under ``shard_map``: the
    TPU compiler cannot partition a Pallas call itself.  KV heads (and
    their query groups) split over ``"model"`` like the pools do
    (distributed/sharding.cache_spec), rows over the data axes; heads are
    independent, so no collective is needed.
    """
    call = functools.partial(_paged_btqd, scale=scale, logit_cap=logit_cap,
                             interpret=resolve_interpret(interpret))
    if mesh is None:
        return call(q, k_pages, v_pages, lengths, table)
    B, _, Hq, _ = q.shape
    Hkv = k_pages.shape[1]
    m = mesh.shape.get("model", 1)
    heads = "model" if m > 1 and Hkv % m == 0 and Hq % m == 0 else None
    d_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    d_size = math.prod(mesh.shape[a] for a in d_axes)
    rows = d_axes if d_axes and d_size > 1 and B % d_size == 0 else None
    pool = P(None, heads, None, None)
    return jax.shard_map(
        call, mesh=mesh,
        in_specs=(P(rows, None, heads, None), pool, pool, P(rows),
                  P(rows, None)),
        out_specs=P(rows, None, heads, None),
        check_vma=False)(q, k_pages, v_pages, lengths, table)
