"""Ragged grouped-matmul Pallas TPU kernels — megablox-style MoE FFN hot path.

Unlike the capacity kernel (gmm.py), tokens are NOT densified into fixed
``(E, C, D)`` bins.  Tokens arrive sorted by expert id; per-expert
``group_offsets`` are scalar-prefetched and drive a grid over
``(n-tiles, m-visits)`` where each m-visit looks up its expert id and row
tile from precomputed group metadata:

  * an m-tile whose rows all belong to one expert is visited once;
  * an m-tile that straddles a group boundary is visited once per group it
    touches, with a row mask so each visit contributes only its own rows;
  * an EMPTY expert contributes zero visits — kernel work scales with the
    actually-routed token count N·K, not with E·C worst-case bins.

The grid's visit axis is padded to the static worst case
``num_m_tiles + E - 1`` (every boundary unaligned); padding visits are
skipped via ``pl.when`` so they cost no MXU work.

``fused_gate_up`` additionally fuses the two up-projections of a
SwiGLU/GeGLU FFN into one launch: each x block is loaded ONCE and both
``x @ w_gate`` and ``x @ w_up`` accumulate into separate VMEM scratch
accumulators; the activation and elementwise product are applied at tile
emission.  Together with the down projection this makes the whole expert
FFN 2 launches instead of 3, halving x HBM reads.

Expert weights reach the kernel as one stack ``(P, E, d, f)`` (``P``
layers) plus an int32 ``layer`` index, scalar-prefetched after the group
metadata: the weight block's index map reads ``(layer, expert, 0, n-tile)``
straight out of the stack.  A layer scan therefore hands the kernel the
whole stacked array and its loop index, never a per-layer slice: XLA fuses
a slice into its own dots, but a Mosaic call needs its operand as a buffer
of its own, so a sliced weight would be copied whole (every expert of the
layer) before each launch.  A plain ``(E, d, f)`` weight is the stack of
one layer, index 0.

Accumulation is fp32; outputs are cast back to the input dtype — the
oracles in ref.py are the parity spec (see tests/test_ragged_gmm.py).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def _pick_tile(dim: int, pref: int) -> int:
    """Largest divisor of ``dim`` that is <= pref (MXU lane tile if possible)."""
    for d in range(min(pref, dim), 0, -1):
        if dim % d == 0:
            return d
    return dim


class GroupMetadata(NamedTuple):
    """Scalar-prefetch operands driving the ragged grid (all int32)."""
    group_offsets: jnp.ndarray   # (E+1,) row offsets of each expert's slab
    group_ids: jnp.ndarray       # (T_max,) expert id per visit
    m_tile_ids: jnp.ndarray      # (T_max+1,) m-tile per visit, -1 sentinel last
    num_visits: jnp.ndarray      # (1,) visits that carry real work


def make_group_metadata(group_sizes: jnp.ndarray, n_rows_pad: int,
                        bm: int) -> GroupMetadata:
    """Map a static ``T_max = n_rows_pad/bm + E - 1`` visit axis onto the
    ragged (expert, m-tile) work list.  ``num_visits`` (dynamic) counts the
    visits that do real work: sum over NON-EMPTY experts of the m-tiles their
    row range touches — the tile-count that scales with N·K, not E·C."""
    E = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    tiles = jnp.where(group_sizes > 0, -(-ends // bm) - starts // bm, 0)
    visit_ends = jnp.cumsum(tiles)
    num_visits = visit_ends[-1]
    t_max = n_rows_pad // bm + E - 1
    t = jnp.arange(t_max)
    g = jnp.minimum(jnp.searchsorted(visit_ends, t, side="right"), E - 1)
    mt = starts[g] // bm + (t - (visit_ends[g] - tiles[g]))
    valid = t < num_visits
    # padding visits replay the last real tile (masked to a no-op) so the
    # "last visit of my tile → emit" test stays a single lookahead
    last_tile = mt[jnp.maximum(num_visits - 1, 0)]
    mt = jnp.where(valid, mt, last_tile)
    g = jnp.where(valid, g, E - 1)
    mt_ext = jnp.concatenate([mt, jnp.full((1,), -1, mt.dtype)])
    offsets = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends])
    return GroupMetadata(offsets.astype(jnp.int32), g.astype(jnp.int32),
                         mt_ext.astype(jnp.int32),
                         num_visits[None].astype(jnp.int32))


def _visit_bookkeeping(offs, gids, mtids, nvis, *, bm: int):
    """(first, valid, row_mask, mt) for the current grid step."""
    i = pl.program_id(1)
    g = gids[i]
    mt = mtids[i]
    valid = i < nvis[0]
    first = (i == 0) | (mtids[jnp.maximum(i - 1, 0)] != mt)
    rows = mt * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    mask = (rows >= offs[g]) & (rows < offs[g + 1])
    return first, valid, mask, mt


def _ragged_kernel(offs, gids, mtids, nvis, layer, x_ref, w_ref, o_ref,
                   acc_ref, *, bm: int):
    first, valid, mask, mt = _visit_bookkeeping(offs, gids, mtids, nvis, bm=bm)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(valid)
    def _accum():
        prod = jnp.dot(x_ref[...].astype(jnp.float32),
                       w_ref[0, 0].astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        acc_ref[...] += jnp.where(mask, prod, 0.0)

    @pl.when(mtids[pl.program_id(1) + 1] != mt)
    def _emit():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _fused_kernel(offs, gids, mtids, nvis, layer, x_ref, wg_ref, wu_ref,
                  o_ref, acc_g, acc_u, *, bm: int, activation: str):
    first, valid, mask, mt = _visit_bookkeeping(offs, gids, mtids, nvis, bm=bm)

    @pl.when(first)
    def _init():
        acc_g[...] = jnp.zeros_like(acc_g)
        acc_u[...] = jnp.zeros_like(acc_u)

    @pl.when(valid)
    def _accum():
        x = x_ref[...].astype(jnp.float32)           # loaded once, used twice
        pg = jnp.dot(x, wg_ref[0, 0].astype(jnp.float32),
                     preferred_element_type=jnp.float32)
        pu = jnp.dot(x, wu_ref[0, 0].astype(jnp.float32),
                     preferred_element_type=jnp.float32)
        acc_g[...] += jnp.where(mask, pg, 0.0)
        acc_u[...] += jnp.where(mask, pu, 0.0)

    @pl.when(mtids[pl.program_id(1) + 1] != mt)
    def _emit():
        # act(0)*0 == 0 for silu/gelu, so never-touched (padding) rows emit 0
        g = acc_g[...]
        act = jax.nn.gelu(g, approximate=True) if activation == "gelu" \
            else jax.nn.silu(g)
        o_ref[...] = (act * acc_u[...]).astype(o_ref.dtype)


def _scalar_maps():
    """Index maps for (x, w, out) blocks given the scalar-prefetch refs
    (group metadata, then the layer index into the weight stack)."""
    x_map = lambda j, i, offs, gids, mtids, nvis, layer: (mtids[i], 0)
    w_map = lambda j, i, offs, gids, mtids, nvis, layer: (layer[0], gids[i],
                                                          0, j)
    o_map = lambda j, i, offs, gids, mtids, nvis, layer: (mtids[i], j)
    return x_map, w_map, o_map


def _stacked(ws, layer):
    """``(ws as (P, E, d, f) stacks, (1,) int32 layer index)``: a 3-D
    ``(E, d, f)`` weight is the stack of one layer, index 0."""
    if ws[0].ndim == 3:
        return tuple(w[None] for w in ws), jnp.zeros((1,), jnp.int32)
    if layer is None:
        raise TypeError("stacked (P, E, d, f) weights need a layer index")
    return ws, jnp.reshape(layer, (1,)).astype(jnp.int32)


def _row_tile(n: int, bm: int, dtype) -> int:
    sub = 16 if dtype == jnp.bfloat16 else 8
    return min(bm, max(sub, _round_up(n, sub)))


def _ragged_call(xs_pad, ws, layer, meta: GroupMetadata, kernel, n_acc: int,
                 out_f: int, *, bm: int, bn: int, interpret: bool):
    """Shared pallas_call plumbing for the single and fused kernels: ``ws``
    are ``(P, E, d, f)`` stacks, ``layer`` the (1,) index into them."""
    n_pad, d = xs_pad.shape
    E = ws[0].shape[1]
    t_max = n_pad // bm + E - 1
    x_map, w_map, o_map = _scalar_maps()
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(out_f // bn, t_max),
            in_specs=[pl.BlockSpec((bm, d), x_map)]
            + [pl.BlockSpec((1, 1, d, bn), w_map) for _ in ws],
            out_specs=pl.BlockSpec((bm, bn), o_map),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)] * n_acc,
        ),
        out_shape=jax.ShapeDtypeStruct((n_pad, out_f), xs_pad.dtype),
        interpret=interpret,
    )(*meta, layer, xs_pad, *ws)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def ragged_gmm(xs: jnp.ndarray,           # (N, D) tokens sorted by expert
               w: jnp.ndarray,            # (E, D, F) or (P, E, D, F)
               group_sizes: jnp.ndarray,  # (E,) rows per expert
               *, layer: Optional[jnp.ndarray] = None,
               bm: int = 128, bn: int = 128,
               interpret: Optional[bool] = None) -> jnp.ndarray:   # (N, F)
    """xs @ w[g] per expert group; a stacked ``w`` is read at ``layer``."""
    (w,), layer = _stacked((w,), layer)
    N, D = xs.shape
    F = w.shape[-1]
    bm = _row_tile(N, bm, xs.dtype)
    bn = _pick_tile(F, bn)
    n_pad = _round_up(N, bm)
    xs_pad = jnp.pad(xs, ((0, n_pad - N), (0, 0)))
    meta = make_group_metadata(group_sizes, n_pad, bm)
    out = _ragged_call(xs_pad, (w,), layer, meta,
                       functools.partial(_ragged_kernel, bm=bm),
                       1, F, bm=bm, bn=bn,
                       interpret=resolve_interpret(interpret))
    return out[:N]


@functools.partial(jax.jit,
                   static_argnames=("activation", "bm", "bn", "interpret"))
def fused_gate_up(xs: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
                  group_sizes: jnp.ndarray, *,
                  layer: Optional[jnp.ndarray] = None,
                  activation: str = "silu", bm: int = 128, bn: int = 128,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """act(xs @ w_gate[g]) * (xs @ w_up[g]) in ONE launch: (N, D) → (N, F).
    Stacked ``(P, E, D, F)`` weights are read at ``layer``."""
    (w_gate, w_up), layer = _stacked((w_gate, w_up), layer)
    N, D = xs.shape
    F = w_gate.shape[-1]
    bm = _row_tile(N, bm, xs.dtype)
    bn = _pick_tile(F, bn)
    n_pad = _round_up(N, bm)
    xs_pad = jnp.pad(xs, ((0, n_pad - N), (0, 0)))
    meta = make_group_metadata(group_sizes, n_pad, bm)
    out = _ragged_call(
        xs_pad, (w_gate, w_up), layer, meta,
        functools.partial(_fused_kernel, bm=bm, activation=activation),
        2, F, bm=bm, bn=bn, interpret=resolve_interpret(interpret))
    return out[:N]


@functools.partial(jax.jit,
                   static_argnames=("activation", "bm", "bn", "interpret"))
def ragged_moe_ffn(xs: jnp.ndarray,       # (N, D) tokens sorted by expert
                   w_gate: jnp.ndarray, w_up: jnp.ndarray,  # ([P,] E, D, F)
                   w_down: jnp.ndarray,   # ([P,] E, F, D)
                   group_sizes: jnp.ndarray, *,
                   layer: Optional[jnp.ndarray] = None,
                   activation: str = "silu", bm: int = 128, bn: int = 128,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """Whole expert FFN on expert-sorted tokens in 2 launches (fused gate+up,
    then down).  Group metadata is built once and shared.  Stacked
    ``(P, E, ...)`` weights are read at ``layer`` inside the kernels, so a
    layer scan passes the stacks and its index, never a slice."""
    (w_gate, w_up, w_down), layer = _stacked((w_gate, w_up, w_down), layer)
    N, D = xs.shape
    F = w_gate.shape[-1]
    bm = _row_tile(N, bm, xs.dtype)
    n_pad = _round_up(N, bm)
    xs_pad = jnp.pad(xs, ((0, n_pad - N), (0, 0)))
    meta = make_group_metadata(group_sizes, n_pad, bm)
    interpret = resolve_interpret(interpret)
    h = _ragged_call(
        xs_pad, (w_gate, w_up), layer, meta,
        functools.partial(_fused_kernel, bm=bm, activation=activation),
        2, F, bm=bm, bn=_pick_tile(F, bn), interpret=interpret)
    y = _ragged_call(h, (w_down,), layer, meta,
                     functools.partial(_ragged_kernel, bm=bm),
                     1, D, bm=bm, bn=_pick_tile(D, bn), interpret=interpret)
    return y[:N]


def _selfcheck() -> None:
    """Interpret-mode parity smoke vs the ref.py oracles (scripts/ci.sh)."""
    import numpy as np

    from repro.kernels.gmm.ref import fused_gate_up_ref, ragged_gmm_ref

    sizes = jnp.array([70, 0, 1, 57], jnp.int32)
    N = int(sizes.sum())
    E, D, F = 4, 64, 96
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    xs = jax.random.normal(ks[0], (N, D), jnp.float32)
    wg = jax.random.normal(ks[1], (E, D, F), jnp.float32) / np.sqrt(D)
    wu = jax.random.normal(ks[2], (E, D, F), jnp.float32) / np.sqrt(D)
    np.testing.assert_allclose(
        np.asarray(ragged_gmm(xs, wg, sizes, interpret=True)),
        np.asarray(ragged_gmm_ref(xs, wg, sizes)), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(fused_gate_up(xs, wg, wu, sizes, interpret=True)),
        np.asarray(fused_gate_up_ref(xs, wg, wu, sizes)),
        rtol=1e-4, atol=1e-4)
    visits = int(make_group_metadata(sizes, _round_up(N, 128), 128).num_visits[0])
    assert visits <= 3 + 1, visits   # 1 full m-tile + 3 boundary straddles
    print(f"ragged kernel parity OK (N={N}, visits={visits})")


if __name__ == "__main__":
    _selfcheck()
