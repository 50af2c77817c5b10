"""Decode / SD-verify attention Pallas TPU kernel.

The paper's verification step attends T = gamma+1 fresh query tokens against
a long KV cache at per-sequence offsets ``lengths`` — this kernel is that
hot spot.  Compared to prefill flash attention:

  * T is tiny (1..8): one q block covers all queries; the q tile is padded
    to the 8-row TPU sublane minimum.
  * masking is ``k_pos <= length + t`` (per sequence, per query row), not a
    static triangle,
  * grid (B, Hkv, S/bk) — KV innermost, online softmax in VMEM scratch; the
    g = Hq/Hkv query heads of a KV head are folded into the q-tile rows
    (rows = g * T_pad), so GQA costs no extra KV traffic.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    len_ref,                   # scalar prefetch: (B,) lengths
    q_ref, k_ref, v_ref, o_ref,
    m_ref, l_ref, acc_ref,
    *,
    bk: int, nk: int, t_pad: int, t_real: int, scale: float, logit_cap: float,
):
    b, ik = pl.program_id(0), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    rows = q_ref.shape[2]                                  # g * t_pad
    # query position per row: length + (row % t_pad), capped by t_real
    row_t = jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 0) % t_pad
    q_pos = length + row_t
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
    valid = (k_pos <= q_pos) & (row_t < t_real)

    def _step():
        q = q_ref[0, 0].astype(jnp.float32)                # (rows, d)
        k = k_ref[0, 0].astype(jnp.float32)                # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if logit_cap > 0:
            s = jnp.tanh(s / logit_cap) * logit_cap
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    # skip KV blocks entirely beyond the newest query position
    pl.when(ik * bk <= length + t_real - 1)(_step)

    @pl.when(ik == nk - 1)
    def _emit():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _paged_decode_kernel(
    len_ref,                   # scalar prefetch: (B,) lengths
    tbl_ref,                   # scalar prefetch: (B * MP,) flattened block table
    q_ref, k_ref, v_ref, o_ref,
    m_ref, l_ref, acc_ref,
    *,
    ps: int, mp: int, t_pad: int, t_real: int, scale: float, logit_cap: float,
):
    """The dense kernel body, one page per KV grid step.  The block table
    is consumed by the K/V index maps: the body only sees logical
    positions, so unallocated entries (the trash page) sit beyond
    ``length + t_real - 1`` and are skipped or masked like any other
    position past the row's length."""
    del tbl_ref
    _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   bk=ps, nk=mp, t_pad=t_pad, t_real=t_real, scale=scale,
                   logit_cap=logit_cap)


@functools.partial(
    jax.jit, static_argnames=("scale", "logit_cap", "interpret"))
def paged_decode_attention_bhtd(
    q: jnp.ndarray,            # (B, Hq, T, D), T = gamma+1 fresh queries
    k_pages: jnp.ndarray,      # (NP, Hkv, ps, D) head-major page pool
    v_pages: jnp.ndarray,
    lengths: jnp.ndarray,      # (B,) committed lengths (queries at length+t)
    table: jnp.ndarray,        # (B, MP) logical page -> physical page
    *,
    scale: float = 0.0,
    logit_cap: float = 0.0,
    interpret: bool = False,
) -> jnp.ndarray:
    """Decode/verify attention reading KV straight from the paged pool.

    The pool is head-major so one (page, head) block is a contiguous
    ``(ps, D)`` tile: its last two block dimensions meet the TPU's
    (sublane, lane) tiling for any ``Hkv``.  (A ``(ps, Hkv, D)`` pool would
    need a block of one head out of ``Hkv`` in the sublane dimension,
    which the compiler refuses unless ``Hkv`` is a multiple of 8.)

    Walks the block table inside the Pallas grid: the K/V index maps look
    the physical page id up in the scalar-prefetched flattened ``table``
    (the ``kernels/gmm/ragged.py`` idiom), so no dense ``pool[table]``
    gather is ever materialized in HBM.  Same online-softmax body and
    masking contract as :func:`decode_attention_bhtd`, with the KV axis
    walked one page per grid step instead of one ``bk`` block.
    """
    B, Hq, T, D = q.shape
    NP, Hkv, ps, _ = k_pages.shape
    MP = table.shape[1]
    g = Hq // Hkv
    if scale == 0.0:
        scale = 1.0 / math.sqrt(D)
    t_pad = max(8 // max(g, 1), T)                          # sublane alignment
    rows = g * t_pad
    # fold (g, T) query heads/steps into rows of one tile
    qf = q.reshape(B, Hkv, g, T, D)
    qf = jnp.pad(qf, ((0, 0), (0, 0), (0, 0), (0, t_pad - T), (0, 0)))
    qf = qf.reshape(B, Hkv, rows, D)
    kernel = functools.partial(
        _paged_decode_kernel, ps=ps, mp=MP, t_pad=t_pad, t_real=T,
        scale=scale, logit_cap=logit_cap)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, Hkv, MP),
            in_specs=[
                pl.BlockSpec((1, 1, rows, D),
                             lambda b, h, j, lens, tbl: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, ps, D),
                             lambda b, h, j, lens, tbl: (tbl[b * MP + j], h, 0, 0)),
                pl.BlockSpec((1, 1, ps, D),
                             lambda b, h, j, lens, tbl: (tbl[b * MP + j], h, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, rows, D),
                                   lambda b, h, j, lens, tbl: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rows, D), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), table.reshape(-1).astype(jnp.int32),
      qf, k_pages, v_pages)
    out = out.reshape(B, Hkv, g, t_pad, D)[:, :, :, :T]
    return out.reshape(B, Hq, T, D)


@functools.partial(
    jax.jit, static_argnames=("scale", "logit_cap", "bk", "interpret"))
def decode_attention_bhtd(
    q: jnp.ndarray,            # (B, Hq, T, D), T = gamma+1 fresh queries
    k: jnp.ndarray,            # (B, Hkv, S, D) cache INCLUDING fresh writes
    v: jnp.ndarray,
    lengths: jnp.ndarray,      # (B,) committed lengths (queries at length+t)
    *,
    scale: float = 0.0,
    logit_cap: float = 0.0,
    bk: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    B, Hq, T, D = q.shape
    _, Hkv, S, _ = k.shape
    g = Hq // Hkv
    if scale == 0.0:
        scale = 1.0 / math.sqrt(D)
    t_pad = max(8 // max(g, 1), T)                          # sublane alignment
    rows = g * t_pad
    # fold (g, T) query heads/steps into rows of one tile
    qf = q.reshape(B, Hkv, g, T, D)
    qf = jnp.pad(qf, ((0, 0), (0, 0), (0, 0), (0, t_pad - T), (0, 0)))
    qf = qf.reshape(B, Hkv, rows, D)
    bk = min(bk, S)
    pad = (-S) % bk
    if pad:  # pad the KV length; padded slots sit beyond every query position
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        S = S + pad
    nk = S // bk
    kernel = functools.partial(
        _decode_kernel, bk=bk, nk=nk, t_pad=t_pad, t_real=T,
        scale=scale, logit_cap=logit_cap)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, nk),
            in_specs=[
                pl.BlockSpec((1, 1, rows, D), lambda b, h, j, lens: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, bk, D), lambda b, h, j, lens: (b, h, j, 0)),
                pl.BlockSpec((1, 1, bk, D), lambda b, h, j, lens: (b, h, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, rows, D), lambda b, h, j, lens: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rows, D), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qf, k, v)
    out = out.reshape(B, Hkv, g, t_pad, D)[:, :, :, :T]
    return out.reshape(B, Hq, T, D)


def _selfcheck() -> None:
    """Interpret-mode parity: paged kernel vs paged oracle vs dense oracle."""
    import numpy as np

    from repro.kernels.decode_attention.ref import (
        decode_attention_ref, paged_decode_attention_ref)

    rng = np.random.default_rng(0)
    for (B, Hq, Hkv, T, D, ps, MP, cap) in [
        (2, 4, 2, 3, 128, 8, 6, 0.0),
        (3, 4, 4, 1, 128, 16, 4, 30.0),
        (1, 8, 2, 5, 128, 64, 3, 0.0),
    ]:
        NP = B * MP + 1                                     # page 0 = trash
        lengths = rng.integers(0, MP * ps - T, size=(B,)).astype(np.int32)
        # each row owns ceil((length+T)/ps) pages; the rest point at trash
        table = np.zeros((B, MP), np.int32)
        nxt = 1
        for b in range(B):
            for lp in range((int(lengths[b]) + T + ps - 1) // ps):
                table[b, lp] = nxt
                nxt += 1
        k_pages = rng.standard_normal((NP, Hkv, ps, D)).astype(np.float32)
        v_pages = rng.standard_normal((NP, Hkv, ps, D)).astype(np.float32)
        q = rng.standard_normal((B, Hq, T, D)).astype(np.float32)
        got = paged_decode_attention_bhtd(
            q, k_pages, v_pages, lengths, table, logit_cap=cap,
            interpret=True)
        want = paged_decode_attention_ref(
            q, k_pages, v_pages, lengths, table, logit_cap=cap)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        # paged oracle == dense oracle on the gathered view
        kd = k_pages[table].transpose(0, 2, 1, 3, 4).reshape(B, Hkv, MP * ps, D)
        vd = v_pages[table].transpose(0, 2, 1, 3, 4).reshape(B, Hkv, MP * ps, D)
        dense = decode_attention_ref(q, kd, vd, lengths, logit_cap=cap)
        np.testing.assert_allclose(want, dense, rtol=2e-5, atol=2e-5)
        print(f"paged_decode_attention ps={ps} MP={MP} B={B} "
              f"Hq/Hkv={Hq}/{Hkv} T={T} cap={cap}: OK")


if __name__ == "__main__":
    _selfcheck()
