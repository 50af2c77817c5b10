"""Unified model: embedding → (optional encoder) → decoder stack → head.

API (all pure functions of params):

    model = Model(cfg)
    params = model.init(key)
    logits, metrics = model.forward_train(params, tokens)
    cache  = model.init_cache(batch, max_seq)
    logits, cache = model.prefill(params, tokens, cache, lengths=...)
    logits, pend  = model.extend(params, tokens, cache, collect=True)
    cache  = model.commit(pend, n_commit)

Cache layout::

    {"layers": [slot_0, ...], "lengths": (B,) int32,
     "cross": [slot_i ...] | None}

``extend`` consumes T tokens per sequence at offsets ``lengths`` — T=1 is
plain autoregressive decode, T=gamma+1 is a speculative-decoding verify
pass.  With ``collect=True`` recurrent slots return per-step states
(leading T axis); ``commit`` gathers the state of the last consumed-and-
accepted token and bumps ``lengths``.  Attention slots are committed in
place (stale entries are masked by position, see attention.py).

Paged KV (``init_cache(..., paged=True)``): full-attention and MLA slots
store their sequence axis in fixed-size pages drawn from a shared pool
(``{"k_pages": (P, NP, H, page, D), ...}``, head-major so one page of
one head is a contiguous tile for the paged kernel) indexed by a per-row block
table ``cache["pages"]["table"]`` (B, max_pages) — physical page 0 is a
reserved trash page that unallocated/retired rows point at, so stale lanes
write harmlessly and no index is ever negative.  The table is DATA: page
assignment (``PageAllocator``) never retraces, only pool growth
(``grow_cache_pages``) does.  SWA rings and recurrent states are bounded
per row already and stay dense inside a paged cache.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.constraints import constrain
from repro.models import attention as attn_mod
from repro.models import transformer as tfm
from repro.models.layers import (
    apply_norm,
    embed,
    init_embedding,
    init_norm,
    sinusoidal_positions,
    unembed,
)
from repro.models.transformer import ATTN_KINDS, RECURRENT_KINDS


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def merge_cache_rows(old: dict, new: dict, mask: jnp.ndarray) -> dict:
    """Row-wise select between two same-shape decode caches.

    The continuous-batching admission primitive (core/spec_decode.
    SDEngine.admit): rows where ``mask`` is True take ``new`` (a freshly
    prefilled cache), all other rows keep ``old`` untouched — so new
    requests enter a live batch without disturbing in-flight sequences and
    without changing any compiled shape.

    Works on the ``{"layers": [...], "lengths": (B,)}`` cache layout:
    ``lengths`` carries batch on axis 0, stack-cache leaves on axis 1
    (leading ``num_periods`` axis — attention K/V, SWA ring ``pos``, MLA
    latents and recurrent states all follow it, see
    transformer.make_stack_cache).  Encoder-decoder ``cross`` caches are
    not supported (continuous admission would need per-row re-encoding).
    """
    if old.get("cross") is not None:
        raise NotImplementedError(
            "merge_cache_rows: encoder-decoder cross caches are static "
            "per-wave; continuous admission is decoder-only")
    if old.get("pages") is not None:
        raise NotImplementedError(
            "merge_cache_rows needs two same-shape caches; a paged cache "
            "admits through scatter_cache_rows (the sliced path)")
    mask = jnp.asarray(mask, bool)

    def pick(o, n):
        shape = [1] * o.ndim
        shape[1] = mask.shape[0]
        return jnp.where(mask.reshape(shape), n, o)

    layers = [jax.tree.map(pick, lo, ln)
              for lo, ln in zip(old["layers"], new["layers"])]
    lengths = jnp.where(mask, new["lengths"], old["lengths"])
    return dict(old, layers=layers, lengths=lengths)


_PAGED_LEAF_PAIRS = (("k_pages", "k"), ("v_pages", "v"),
                     ("latent_pages", "latent"), ("k_rope_pages", "k_rope"))


def scatter_cache_rows(old: dict, new: dict, rows: jnp.ndarray, *,
                       valid: Optional[jnp.ndarray] = None,
                       n_prompt: Optional[int] = None) -> dict:
    """Row-scatter a COMPACT (R-row) cache into a live B-row cache.

    The row-sliced admission primitive (core/spec_decode.SDEngine.
    admit_rows): ``new`` is a freshly prefilled cache holding only the R
    admitted rows, ``rows`` (R,) the pool row index each goes to.  Unlike
    :func:`merge_cache_rows` the fresh prefill's shape is (R, ...) — its
    cost scales with what was admitted, not the pool.

    ``valid`` (R,) bool marks real lanes; padding lanes (row-count
    bucketing replicates admissions round-robin, and at temperature>0 the
    replicas sample different first tokens) are dropped from the scatter so
    results never depend on lane order.  ``rows`` itself is data — which
    rows get admitted never retraces.

    Dense leaves (batch on axis 1, like merge_cache_rows) scatter whole
    rows.  Paged leaves scatter the first ``n_prompt`` positions (rounded
    up to whole pages) through ``old["pages"]["table"]``; the admitted
    row's decode-region pages are left stale — decode writes its positions
    before attending, the same discipline that makes rejected SD suffixes
    safe.  ``new`` must be a DENSE cache whose max_seq matches the live
    cache's logical capacity (so SWA ring widths line up).
    """
    if old.get("cross") is not None:
        raise NotImplementedError(
            "scatter_cache_rows: continuous admission is decoder-only")
    rows = jnp.asarray(rows, jnp.int32)
    R = rows.shape[0]
    B = old["lengths"].shape[0]
    if valid is None:
        valid = jnp.ones((R,), bool)
    else:
        valid = jnp.asarray(valid, bool)
    # invalid lanes target index B — out of bounds, dropped by the scatter
    rows_eff = jnp.where(valid, rows, B)
    table = None if old.get("pages") is None else old["pages"]["table"]

    def scatter_dense(o, n):
        return o.at[:, rows_eff].set(n, mode="drop")

    def scatter_paged(o, n):
        # o: (P, NP, ..., ps, dim), page slots second to last like every
        # pool (attention._paged_write); n: (P, R, S_f, ...) — write the
        # prompt pages of each admitted row through the block table
        ps = o.shape[-2]
        S_f = n.shape[2]
        span = S_f if n_prompt is None else min(-(-n_prompt // ps) * ps, S_f)
        pos = jnp.arange(span)
        pid = table[rows[:, None], (pos // ps)[None, :]]        # (R, span)
        pid = jnp.where(valid[:, None], pid, o.shape[1])        # drop pads
        # layer, page and slot all index arrays: the indexed view is
        # (P, R, span, ...) like ``n`` with or without a head axis between
        layer = jnp.arange(o.shape[0])[:, None, None]
        heads = (slice(None),) * (o.ndim - 4)
        return o.at[(layer, pid[None], *heads, (pos % ps)[None, None])].set(
            n[:, :, :span], mode="drop")

    paged_to_dense = dict(_PAGED_LEAF_PAIRS)
    layers = []
    for lo, ln in zip(old["layers"], new["layers"]):
        slot = {k: (scatter_paged(leaf, ln[paged_to_dense[k]])
                    if k in paged_to_dense else scatter_dense(leaf, ln[k]))
                for k, leaf in lo.items()}
        layers.append(slot)
    lengths = old["lengths"].at[rows_eff].set(new["lengths"], mode="drop")
    return dict(old, layers=layers, lengths=lengths)


class PageAllocator:
    """Host-side block manager for a paged decode cache.

    Mirrors TensorRT-LLM's KV block manager at the granularity this repo
    needs: a free list over the physical pool, per-row page ownership, and
    a (B, max_pages) logical→physical table the jitted forwards consume as
    DATA.  Physical page 0 is the trash page — never allocated, the target
    of every unassigned table entry — so retired rows' frozen-lane writes
    land harmlessly and reads stay in bounds.

    ``alloc``/``free_row`` mutate ``self.table`` in place; callers push
    ``jnp.asarray(alloc.table)`` back into the session state after a
    change (an input-array swap, never a retrace).  When ``can_alloc``
    says no, ``grown_geometry`` returns the next pow2 (pool_pages,
    max_pages) to rebuild with via :func:`grow_cache_pages`.

    Pages are REFCOUNTED so rows can share a prompt prefix
    (:meth:`fork_prefix`): a shared page appears in several rows' tables
    and ``owned`` lists but returns to the free list only when its last
    reference drops (:meth:`free_row`).  A sharing row that must write
    into a shared page first detaches it via copy-on-write
    (:meth:`cow_range`), which hands the caller the (src, dst) physical
    pairs to copy device-side (:func:`copy_cache_pages`) before any
    write lands.
    """

    def __init__(self, batch: int, page_size: int, pool_pages: int,
                 max_pages: int):
        import numpy as np
        self.page_size = int(page_size)
        self.pool_pages = int(pool_pages)
        self.max_pages = int(max_pages)
        self.free: List[int] = list(range(1, self.pool_pages))
        self.owned: Dict[int, List[int]] = {}
        self.reserved: List[int] = []
        self.ref: Dict[int, int] = {}
        self.table = np.zeros((batch, self.max_pages), np.int32)

    def pages_for(self, n_positions: int) -> int:
        return -(-int(n_positions) // self.page_size)

    def can_alloc(self, n_positions: int) -> bool:
        need = self.pages_for(n_positions)
        return need <= len(self.free) and need <= self.max_pages

    def alloc(self, row: int, n_positions: int) -> None:
        """Assign pages covering ``n_positions`` to ``row`` (must be free)."""
        need = self.pages_for(n_positions)
        if row in self.owned:
            raise ValueError(f"row {row} already owns pages; free_row first")
        if need > len(self.free) or need > self.max_pages:
            raise ValueError(
                f"cannot allocate {need} pages (free={len(self.free)}, "
                f"max_pages={self.max_pages}); grow the pool first")
        pages = [self.free.pop() for _ in range(need)]
        for p in pages:
            self.ref[p] = 1
        self.owned[row] = pages
        self.table[row, :] = 0
        self.table[row, :need] = pages

    def fork_prefix(self, src: int, dst: int, n_positions: int) -> int:
        """Share ``src``'s pages covering its first ``n_positions`` with
        ``dst`` (must own nothing): each shared page's refcount bumps and
        appears in ``dst``'s table — zero device traffic, the pool is
        untouched.  ``dst`` must not write inside the shared range without
        first detaching via :meth:`cow_range`.  Returns the number of
        pages shared."""
        need = self.pages_for(n_positions)
        if dst in self.owned:
            raise ValueError(f"row {dst} already owns pages; free_row first")
        src_pages = self.owned.get(src)
        if src_pages is None or len(src_pages) < need:
            raise ValueError(
                f"row {src} owns {0 if src_pages is None else len(src_pages)}"
                f" pages, cannot share {need}")
        pages = list(src_pages[:need])
        for p in pages:
            self.ref[p] += 1
        self.owned[dst] = pages
        self.table[dst, :] = 0
        self.table[dst, :need] = pages
        return need

    def extend_row(self, row: int, n_positions: int) -> int:
        """Grow ``row``'s ownership with private pages until it covers
        ``n_positions`` total (the fork_prefix companion: shared prefix
        pages + private tail).  Returns the number of pages added."""
        if row not in self.owned:
            raise ValueError(f"row {row} owns no pages; alloc or "
                             "fork_prefix first")
        need = self.pages_for(n_positions)
        have = len(self.owned[row])
        extra = need - have
        if extra <= 0:
            return 0
        if extra > len(self.free) or need > self.max_pages:
            raise ValueError(
                f"cannot extend row {row} by {extra} pages "
                f"(free={len(self.free)}, max_pages={self.max_pages})")
        pages = [self.free.pop() for _ in range(extra)]
        for p in pages:
            self.ref[p] = 1
        self.owned[row].extend(pages)
        self.table[row, have:need] = pages
        return extra

    def cow_range(self, row: int, start: int, end: int) -> List[Tuple[int, int]]:
        """Detach every SHARED page of ``row`` covering logical positions
        [start, end): each gets a fresh private physical page swapped into
        the row's table/ownership (old refcount drops).  Returns the
        (src, dst) physical pairs; the caller MUST device-copy src→dst
        across all paged leaves (:func:`copy_cache_pages`) before writing,
        or the row loses its shared-prefix content.  Pages already private
        (ref == 1) are left alone."""
        pages = self.owned.get(row, [])
        pairs: List[Tuple[int, int]] = []
        lp0 = int(start) // self.page_size
        lp1 = min(-(-int(end) // self.page_size), len(pages))
        for lp in range(max(lp0, 0), lp1):
            p = pages[lp]
            if self.ref[p] > 1:
                if not self.free:
                    raise ValueError(
                        f"cow_range: no free page to detach page {p} of "
                        f"row {row}; grow the pool first")
                fresh = self.free.pop()
                self.ref[p] -= 1
                self.ref[fresh] = 1
                pages[lp] = fresh
                self.table[row, lp] = fresh
                pairs.append((p, fresh))
        return pairs

    def shared_page_count(self) -> int:
        """Number of physical pages currently referenced by more than one
        row — the pool-side prefix-sharing win ``assert_no_leaks`` and the
        serving stats report."""
        return sum(1 for c in self.ref.values() if c > 1)

    def free_row(self, row: int) -> None:
        """Drop ``row``'s references; pages return to the pool only at
        refcount zero.  Its table goes to trash.

        Freeing a row that owns nothing is a no-op (retired filler rows
        never allocated), but a page that is ALREADY free or untracked —
        ownership bookkeeping corrupted somewhere — raises instead of
        silently double-crediting the free list.  Pages still shared with
        sibling rows (refcount > 1) stay out of the free list, so
        preempting one fork never yanks a prefix out from under the
        others."""
        pages = self.owned.pop(row, [])
        for p in pages:
            c = self.ref.get(p)
            if c is None or p in self.free:
                raise ValueError(
                    f"double free: row {row} page {p} is already "
                    "free/untracked — page ownership is corrupted")
            if c > 1:
                self.ref[p] = c - 1
            else:
                del self.ref[p]
                self.free.append(p)
        self.table[row, :] = 0

    def free_fraction(self) -> float:
        """Fraction of allocatable pages (trash page excluded) currently
        free — the quantity admission watermarks compare against."""
        return len(self.free) / max(self.pool_pages - 1, 1)

    def reserve(self, n: int) -> List[int]:
        """Withdraw ``n`` pages from the free list without assigning them
        to any row (fault injection / headroom holds).  Reserved pages
        are real pressure: ``can_alloc``/``alloc`` cannot see them until
        :meth:`release` returns them."""
        if n > len(self.free):
            raise ValueError(f"cannot reserve {n} pages ({len(self.free)} "
                             "free)")
        pages = [self.free.pop() for _ in range(n)]
        self.reserved.extend(pages)
        return pages

    def release(self, pages: List[int]) -> None:
        """Return pages taken by :meth:`reserve`.  Releasing a page that
        was never reserved — or releasing twice — raises: that is a
        double free in the making."""
        for p in pages:
            if p not in self.reserved:
                raise ValueError(f"release of page {p} that is not "
                                 "reserved (double release?)")
            self.reserved.remove(p)
            if p in self.free:
                raise ValueError(f"double free: page {p} already in the "
                                 "free list")
            self.free.append(p)

    def assert_no_leaks(self) -> None:
        """End-of-stream invariant: every page is back in the free list.

        After a stream's final ``_free_retired`` (and the fault
        injector's ``release_all``) no row may own pages, no reservation
        may be outstanding, the free list must hold exactly
        ``pool_pages - 1`` pages (all but trash page 0), and every table
        entry must point at trash.  Raises ``RuntimeError`` listing every
        violated condition — leaked pages are how long-running serving
        pools die slowly."""
        import numpy as np
        problems = []
        if self.owned:
            problems.append(f"rows still own pages: {sorted(self.owned)}")
        if self.reserved:
            problems.append(f"outstanding reservations: "
                            f"{sorted(self.reserved)}")
        if len(self.free) != self.pool_pages - 1:
            problems.append(f"free list has {len(self.free)} pages, "
                            f"expected {self.pool_pages - 1}")
        if len(set(self.free)) != len(self.free):
            problems.append("free list contains duplicates")
        if self.ref:
            shared = self.shared_page_count()
            problems.append(
                f"{len(self.ref)} pages still refcounted "
                f"({shared} of them shared): {sorted(self.ref)[:16]}")
        if self.table.any():
            rows = sorted(set(np.nonzero(self.table)[0].tolist()))
            problems.append(f"table rows still mapped: {rows}")
        if problems:
            raise RuntimeError("PageAllocator leak check failed: "
                               + "; ".join(problems))

    def grown_geometry(self, n_positions: int) -> Tuple[int, int]:
        """(pool_pages, max_pages) after pow2 growth that fits an
        allocation of ``n_positions`` more positions."""
        need = self.pages_for(n_positions)
        max_pages = self.max_pages
        while need > max_pages:
            max_pages *= 2
        pool = self.pool_pages
        while need > pool - 1 - (self.pool_pages - 1 - len(self.free)):
            pool *= 2
        return pool, max_pages

    def grow(self, pool_pages: int, max_pages: int) -> None:
        """Adopt a grown geometry (pool/table already padded by
        :func:`grow_cache_pages` on the device side)."""
        import numpy as np
        assert pool_pages >= self.pool_pages and max_pages >= self.max_pages
        self.free.extend(range(self.pool_pages, pool_pages))
        self.table = np.pad(self.table,
                            ((0, 0), (0, max_pages - self.max_pages)))
        self.pool_pages, self.max_pages = pool_pages, max_pages


@functools.lru_cache(maxsize=None)
def _pad_tail_fn(ndim: int, axis: int, extra: int):
    # eager jnp.pad materializes its pad config as implicit host->device
    # scalar transfers on every call; growth runs between rounds on the
    # transfer-guarded serving path, so bake the geometry into a cached
    # jitted pad (jax then keys compilations on the leaf aval as usual)
    pad = [(0, 0)] * ndim
    pad[axis] = (0, extra)
    pad = tuple(pad)
    return jax.jit(lambda x: jnp.pad(x, pad))


def _pad_tail(leaf, axis: int, extra: int):
    """Zero-pad ``leaf`` by ``extra`` trailing slots along ``axis``."""
    return _pad_tail_fn(leaf.ndim, axis, extra)(leaf)


def grow_cache_pages(cache: dict, pool_pages: int, max_pages: int) -> dict:
    """Pad a paged cache to a larger pool / logical capacity.

    Pool leaves pad along the physical-page axis, the block table along
    the logical-page axis (new entries point at trash page 0).  Dense
    leaves inside the paged cache (SWA rings, recurrent states, lengths)
    are untouched — their per-row footprint is position-count independent.
    A growth changes leaf SHAPES, so the next round/admit call retraces:
    that is the amortized price of not sizing ``max_seq`` for the
    worst-case request up front.
    """
    if cache.get("pages") is None:
        raise ValueError("grow_cache_pages: not a paged cache")

    def grow_slot(slot):
        out = dict(slot)
        for paged_key, _ in _PAGED_LEAF_PAIRS:
            if paged_key in slot:
                leaf = slot[paged_key]
                extra = pool_pages - leaf.shape[1]
                if extra:
                    out[paged_key] = _pad_tail(leaf, 1, extra)
        return out

    table = cache["pages"]["table"]
    extra_lp = max_pages - table.shape[1]
    if extra_lp:
        table = _pad_tail(table, 1, extra_lp)
    return dict(cache, layers=[grow_slot(s) for s in cache["layers"]],
                pages=dict(cache["pages"], table=table))


def copy_cache_pages(cache: dict, pairs) -> dict:
    """Device-copy physical pages src→dst across every paged leaf.

    The copy-on-write materialization step: after
    :meth:`PageAllocator.cow_range` hands back (src, dst) physical page
    pairs, this clones their contents so the detached row keeps its
    shared-prefix KV.  Callers pad ``pairs`` to a bucketed count with
    (0, 0) entries — a trash-page self-copy is a harmless no-op — so the
    eager scatter keeps a stable shape across rounds.
    """
    if cache.get("pages") is None:
        raise ValueError("copy_cache_pages: not a paged cache")
    if not pairs:
        return cache
    src = jnp.asarray([p[0] for p in pairs], jnp.int32)
    dst = jnp.asarray([p[1] for p in pairs], jnp.int32)

    def copy_slot(slot):
        out = dict(slot)
        for paged_key, _ in _PAGED_LEAF_PAIRS:
            if paged_key in slot:
                leaf = slot[paged_key]
                out[paged_key] = leaf.at[:, dst].set(leaf[:, src])
        return out

    return dict(cache, layers=[copy_slot(s) for s in cache["layers"]])


def grow_cache_seq(cache: dict, cfg: ModelConfig, new_max_seq: int) -> dict:
    """Pad a DENSE cache's sequence axis to ``new_max_seq``.

    The draft-side companion of :func:`grow_cache_pages`: when a paged
    target session grows its logical capacity, the (cheap, dense) proposer
    caches must be able to address the same positions.  Full-attention K/V
    and MLA latents pad along axis 2 (leading period, batch axes);
    recurrent states and lengths have no sequence axis.  SWA rings only
    match if the window already fit the old capacity (a ring resize would
    remap ``pos % w`` slots of live data — unsupported, fail loudly).
    """
    from repro.models.attention import SWA_RING_PAD

    def grow_slot(slot, kind):
        if kind not in ATTN_KINDS:
            return slot
        if kind == "swa":
            w_new = min(cfg.sliding_window + SWA_RING_PAD, new_max_seq)
            if slot["k"].shape[2] != w_new:
                raise NotImplementedError(
                    "grow_cache_seq: SWA ring resize would remap live "
                    "slots; size the stream so capacity >= window + pad")
            return slot
        out = {}
        for k, leaf in slot.items():
            extra = new_max_seq - leaf.shape[2]
            if extra > 0:
                leaf = _pad_tail(leaf, 2, extra)
            out[k] = leaf
        return out

    layers = [grow_slot(s, kind)
              for s, kind in zip(cache["layers"], cfg.layer_pattern)]
    return dict(cache, layers=layers)


def _page_table(cache: dict) -> Optional[jnp.ndarray]:
    pages = cache.get("pages")
    return None if pages is None else pages["table"]


def sinusoidal_at(positions: jnp.ndarray, d_model: int) -> jnp.ndarray:
    """Sinusoidal embedding evaluated at arbitrary positions (B,T) → (B,T,d)."""
    pos = positions.astype(jnp.float32)[..., None]
    i = jnp.arange(d_model // 2, dtype=jnp.float32)
    ang = pos / jnp.power(10_000.0, 2 * i / d_model)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


class Model:
    """Architecture-agnostic decoder(-encoder) language model."""

    def __init__(
        self,
        cfg: ModelConfig,
        *,
        moe_dispatch: str = "onehot",
        use_flash: bool = False,
        remat: bool = False,
        paged_attention: str = "kernel",
        mesh=None,
        mesh_layout: Optional[str] = None,
    ):
        if paged_attention not in ("kernel", "gather"):
            raise ValueError(
                f"paged_attention must be 'kernel' or 'gather', got "
                f"{paged_attention!r}")
        self.cfg = cfg
        self.moe_dispatch = moe_dispatch
        self.use_flash = use_flash
        self.remat = remat
        self.paged_attention = paged_attention
        # explicit mesh threading (docs/distributed.md): flows to every
        # constrain() and to the "ep" dispatch; None = single-device —
        # there is no process-global fallback
        if mesh is not None:
            from repro.distributed.constraints import resolve_mesh
            mesh, mesh_layout = resolve_mesh(mesh, mesh_layout)
        self.mesh = mesh
        self.mesh_layout = mesh_layout

    # ------------------------------------------------------------------ init
    def init(self, key: jax.Array) -> dict:
        cfg = self.cfg
        dt = _dtype(cfg)
        k_emb, k_stack, k_enc, k_head, k_fn = jax.random.split(key, 5)
        params: Dict[str, Any] = {
            "embed": init_embedding(k_emb, cfg.vocab_size, cfg.d_model, dt),
            "final_norm": init_norm(cfg, dt),
            "layers": tfm.init_stack(k_stack, cfg, dt, cross=cfg.is_encoder_decoder),
        }
        if not cfg.tie_embeddings:
            params["head"] = init_embedding(k_head, cfg.vocab_size, cfg.d_model, dt)
        if cfg.is_encoder_decoder:
            enc_cfg = cfg.with_overrides(
                num_layers=cfg.encoder_layers,
                layer_pattern=("attn",),
                moe_pattern=(False,),
                num_experts=0, num_experts_per_tok=0,
            )
            params["encoder"] = {
                "layers": tfm.init_stack(k_enc, enc_cfg, dt, cross=False),
                "final_norm": init_norm(enc_cfg, dt),
            }
            self._enc_cfg = enc_cfg
        return params

    @property
    def enc_cfg(self):
        cfg = self.cfg
        return cfg.with_overrides(
            num_layers=cfg.encoder_layers, layer_pattern=("attn",),
            moe_pattern=(False,), num_experts=0, num_experts_per_tok=0)

    # ----------------------------------------------------------------- embed
    def _embed(self, params, tokens, positions, inputs_embeds=None):
        cfg = self.cfg
        if inputs_embeds is not None:
            x = inputs_embeds.astype(_dtype(cfg))
        else:
            x = embed(params["embed"], tokens, scale=cfg.name.startswith("gemma"))
        if cfg.rope_type == "sinusoidal":
            x = x + sinusoidal_at(positions, cfg.d_model).astype(x.dtype)
        return constrain(x, "hidden", mesh=self.mesh, layout=self.mesh_layout)

    def _head(self, params, x):
        cfg = self.cfg
        x = apply_norm(params["final_norm"], x, cfg.norm_eps)
        table = params["embed"] if cfg.tie_embeddings else params["head"]
        return constrain(unembed(table, x, cfg.final_logit_softcap), "logits",
                         mesh=self.mesh, layout=self.mesh_layout)

    # --------------------------------------------------------------- encoder
    def encode(self, params, encoder_embeds: jnp.ndarray) -> jnp.ndarray:
        """Whisper-style encoder over stub frame embeddings (B, S_enc, d)."""
        cfg = self.enc_cfg
        B, S, _ = encoder_embeds.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        x = encoder_embeds.astype(_dtype(cfg))
        x = x + sinusoidal_at(positions, cfg.d_model).astype(x.dtype)
        x, _, _ = tfm.stack_forward(
            params["encoder"]["layers"], cfg, x, positions, None,
            mode="train", causal=False, use_flash=False, remat=self.remat)
        return apply_norm(params["encoder"]["final_norm"], x, cfg.norm_eps)

    def _cross_kvs(self, params, enc_out):
        """Project encoder output through every decoder layer's cross-attn."""
        cfg = self.cfg
        out = []
        for i in range(cfg.period):
            slot = params["layers"][i]["cross"]
            kv = jax.vmap(
                lambda p: attn_mod.cross_attn_prefill_cache(p, cfg, enc_out, _dtype(cfg))
            )(slot)
            out.append(kv)
        return out

    # ----------------------------------------------------------------- train
    def forward_hidden(
        self,
        params,
        tokens: jnp.ndarray,                       # (B, T)
        *,
        inputs_embeds: Optional[jnp.ndarray] = None,
        encoder_embeds: Optional[jnp.ndarray] = None,
        mrope_positions: Optional[jnp.ndarray] = None,
    ) -> Tuple[jnp.ndarray, dict]:
        """Final pre-head hidden states (B, T, d) + MoE metrics.  The head is
        applied separately (chunked in training) so (B, T, vocab) logits are
        never materialized for long sequences."""
        cfg = self.cfg
        B, T = tokens.shape[:2] if tokens is not None else inputs_embeds.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        x = self._embed(params, tokens, positions, inputs_embeds)
        cross_kvs = None
        if cfg.is_encoder_decoder:
            enc_out = self.encode(params, encoder_embeds)
            cross_kvs = self._cross_kvs(params, enc_out)
        x, _, metrics = tfm.stack_forward(
            params["layers"], cfg, x, positions, None,
            mode="train", dispatch=self.moe_dispatch, use_flash=self.use_flash,
            remat=self.remat, cross_kvs=cross_kvs,
            mrope_positions=mrope_positions,
            mesh=self.mesh, mesh_layout=self.mesh_layout)
        return x, metrics

    def forward_train(self, params, tokens, **kw) -> Tuple[jnp.ndarray, dict]:
        x, metrics = self.forward_hidden(params, tokens, **kw)
        return self._head(params, x), metrics

    # ----------------------------------------------------------------- cache
    def init_cache(self, batch: int, max_seq: int, *, paged: bool = False,
                   page_size: int = 64,
                   pool_pages: Optional[int] = None) -> dict:
        """Allocate a decode cache.

        Dense (default): every attention slot holds (B, max_seq) K/V.
        ``paged=True``: full-attn/MLA slots share a physical page pool of
        ``pool_pages`` pages of ``page_size`` positions (default: enough
        for every row at ``max_seq``, plus the trash page), addressed
        through ``cache["pages"]["table"]`` (B, ceil(max_seq/page_size)).
        ``max_seq`` becomes the LOGICAL capacity — growable later via
        :func:`grow_cache_pages` without resizing any row.
        """
        cfg = self.cfg
        dt = _dtype(cfg)
        cache: Dict[str, Any] = {
            "layers": tfm.make_stack_cache(cfg, batch, max_seq, dt,
                                           paged=paged, page_size=page_size,
                                           pool_pages=pool_pages),
            "lengths": jnp.zeros((batch,), jnp.int32),
        }
        if paged:
            max_pages = -(-max_seq // page_size)
            cache["pages"] = {
                "table": jnp.zeros((batch, max_pages), jnp.int32)}
        return cache

    # --------------------------------------------------------------- prefill
    def prefill(
        self,
        params,
        tokens: jnp.ndarray,                       # (B, T) padded prompts
        cache: dict,
        *,
        lengths: Optional[jnp.ndarray] = None,     # (B,) true prompt lengths
        inputs_embeds: Optional[jnp.ndarray] = None,
        encoder_embeds: Optional[jnp.ndarray] = None,
        mrope_positions: Optional[jnp.ndarray] = None,
    ) -> Tuple[jnp.ndarray, dict]:
        """Returns logits at each sequence's last prompt position (B, V)."""
        last, _, new_cache = self.prefill_with_hidden(
            params, tokens, cache, lengths=lengths,
            inputs_embeds=inputs_embeds, encoder_embeds=encoder_embeds,
            mrope_positions=mrope_positions)
        return last, new_cache

    def prefill_with_hidden(
        self,
        params,
        tokens: jnp.ndarray,                       # (B, T) padded prompts
        cache: dict,
        *,
        lengths: Optional[jnp.ndarray] = None,     # (B,) true prompt lengths
        inputs_embeds: Optional[jnp.ndarray] = None,
        encoder_embeds: Optional[jnp.ndarray] = None,
        mrope_positions: Optional[jnp.ndarray] = None,
    ) -> Tuple[jnp.ndarray, jnp.ndarray, dict]:
        """prefill() variant also returning the pre-head hidden state at each
        sequence's last prompt position (B, d) — the feature carry consumed
        by hidden-feeding proposers (core/eagle.EagleProposer)."""
        cfg = self.cfg
        B, T = tokens.shape
        if lengths is None:
            lengths = jnp.full((B,), T, jnp.int32)
        positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        x = self._embed(params, tokens, positions, inputs_embeds)
        cross_kvs = None
        if cfg.is_encoder_decoder:
            enc_out = self.encode(params, encoder_embeds)
            cross_kvs = self._cross_kvs(params, enc_out)
            cache = dict(cache, cross=cross_kvs)
        x, new_layers, _ = tfm.stack_forward(
            params["layers"], cfg, x, positions, cache["layers"],
            mode="prefill", dispatch=self.moe_dispatch, want_metrics=False,
            use_flash=self.use_flash, remat=self.remat, cross_kvs=cross_kvs,
            mrope_positions=mrope_positions, page_table=_page_table(cache),
            paged_attention=self.paged_attention,
            mesh=self.mesh, mesh_layout=self.mesh_layout)
        # head only at each sequence's last prompt position — never (B,T,V)
        last_h = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)
        last = self._head(params, last_h)[:, 0]
        new_cache = dict(cache, layers=new_layers,
                         lengths=lengths.astype(jnp.int32))
        return last, last_h[:, 0], new_cache

    # ---------------------------------------------------------------- extend
    def _extend_impl(self, params, tokens, cache, *, collect=False,
                     prefetch_masks=None):
        """Shared decode/verify forward behind the three extend variants.

        decode/verify never consumes router metrics — want_metrics=False
        skips the (N, K, E) one-hot aux-loss/expert-count tensors that the
        SD verify hot path would otherwise materialize every round.
        """
        cfg = self.cfg
        B, T = tokens.shape
        positions = cache["lengths"][:, None] + jnp.arange(T)[None, :]
        x = self._embed(params, tokens, positions)
        x, new_layers, metrics = tfm.stack_forward(
            params["layers"], cfg, x, positions, cache["layers"],
            mode="extend", collect=collect, dispatch=self.moe_dispatch,
            want_metrics=False, use_flash=self.use_flash,
            cross_kvs=cache.get("cross"), prefetch_masks=prefetch_masks,
            page_table=_page_table(cache),
            paged_attention=self.paged_attention,
            mesh=self.mesh, mesh_layout=self.mesh_layout)
        logits = self._head(params, x)                           # (B, T, V)
        return logits, x, dict(cache, layers=new_layers), metrics

    def extend(
        self,
        params,
        tokens: jnp.ndarray,                       # (B, T) new tokens
        cache: dict,
        *,
        collect: bool = False,
    ) -> Tuple[jnp.ndarray, dict]:
        """Decode/verify T tokens per sequence at offsets ``lengths``.

        NOTE on recurrent prefill semantics: prefill must be called with
        unpadded (equal-length) prompts for recurrent archs, since states
        advance strictly sequentially.
        """
        logits, _, pend, _ = self._extend_impl(params, tokens, cache,
                                               collect=collect)
        return logits, pend

    @property
    def counts_experts(self) -> bool:
        """True when :meth:`verify` reports ``experts_reached``: the model
        has routed-MoE layers and dispatches them on this device ("gmm" /
        "onehot"; the "ep" dispatch routes inside its shards)."""
        return any(self.cfg.moe_pattern) and self.moe_dispatch != "ep"

    def verify(self, params, tokens, cache, *, plan=None,
               collect: bool = False):
        """The SD verify forward: :meth:`extend` plus the routing counts.

        Parameters
        ----------
        params, tokens, cache
            As :meth:`extend`; ``tokens`` is the (B, gamma+1) verify stream.
        plan : models.moe.PrefetchPlan, optional
            The warm plan built from the draft token stream; when given,
            each MoE layer also scores it against the experts it routed to.
        collect : bool
            As :meth:`extend` (recurrent per-step state collection).

        Returns
        -------
        logits : jnp.ndarray
            (B, T, V) next-token logits.
        hidden : jnp.ndarray
            (B, T, d) final pre-head hidden states (for hidden-feeding
            proposers; dead code otherwise).
        pend : dict
            Pending cache for :meth:`commit`.
        counts : dict
            int32 scalars summed over all MoE layers and periods:
            ``experts_reached`` (the distinct experts each layer's routing
            of all B*T rows reached; only when :attr:`counts_experts`) and,
            with a ``plan``, the prefetch ``hits``/``actual``/``predicted``
            (``actual`` is ``experts_reached`` on the "gmm"/"onehot"
            dispatches).
        """
        logits, x, pend, metrics = self._extend_impl(
            params, tokens, cache, collect=collect,
            prefetch_masks=None if plan is None else list(plan.masks))
        counts = {}
        if self.counts_experts:
            counts["experts_reached"] = metrics["experts_reached"]
        if plan is not None:
            counts.update({k: metrics[f"prefetch_{k}"]
                           for k in ("hits", "actual", "predicted")})
        return logits, x, pend, counts

    def extend_with_hidden(self, params, tokens, cache, *, collect=False):
        """extend() variant that also returns the final hidden states
        (B, T, d) — consumed by EAGLE-style speculation heads
        (core/eagle.py), which predict the NEXT token's features from the
        target's current features."""
        logits, x, pend, _ = self._extend_impl(params, tokens, cache,
                                               collect=collect)
        return logits, x, pend

    # ---------------------------------------------------------------- commit
    def commit(self, pend: dict, n_commit: jnp.ndarray, collected: bool = False) -> dict:
        """Accept ``n_commit`` (B,) tokens of the last extend.

        Attention slots: lengths bump only (stale K/V masked out).
        Recurrent slots (when ``collected``): gather state index
        ``n_commit - 1`` per sequence from the (T, B, ...) pending stack.
        """
        cfg = self.cfg
        new_layers = []
        for i, kind in enumerate(cfg.layer_pattern):
            slot = pend["layers"][i]
            if kind in RECURRENT_KINDS and collected:
                idx = n_commit - 1                                # (B,)

                def gather(a):
                    # a: (P, T, B, ...) → (P, B, ...) selecting per-seq step
                    moved = jnp.moveaxis(a, 2, 0)                 # (B, P, T, ...)
                    sel = jax.vmap(lambda ab, n: ab[:, n])(moved, idx)
                    return jnp.moveaxis(sel, 0, 1)                # (P, B, ...)

                new_layers.append(jax.tree.map(gather, slot))
            else:
                new_layers.append(slot)
        return dict(pend, layers=new_layers,
                    lengths=pend["lengths"] + n_commit.astype(jnp.int32))

    # ------------------------------------------------------------ decode 1tk
    def decode_step(self, params, token: jnp.ndarray, cache: dict):
        """Plain AR decode of one token per sequence. token: (B,) → (B,V)."""
        logits, pend = self.extend(params, token[:, None], cache, collect=True)
        cache = self.commit(pend, jnp.ones_like(cache["lengths"]), collected=True)
        return logits[:, 0], cache


def build_model(cfg: ModelConfig, **kw) -> Model:
    return Model(cfg, **kw)
