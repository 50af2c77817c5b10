"""Paged decode-attention kernel: differential parity at every level.

The block-table-walking Pallas kernel (kernels/decode_attention/) must be
numerically indistinguishable from the dense ``pool[table]`` gather it
replaces AND from a contiguous dense cache — at the kernel level (vs the
jnp oracles, across page sizes, ragged lengths, verify widths, logit
caps, shared tables and grown pools) and at the token level (greedy SD
rounds commit identical tokens through ``SDEngine`` under kernel /
gather / dense caches, including SWA ring layers and mid-stream pool
growth).  docs/paged_attention.md specifies the contract.
"""
from dataclasses import replace as dc_replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs.base import ModelConfig
from repro.core.proposer import ModelProposer
from repro.core.spec_decode import SDEngine
from repro.kernels.decode_attention.ops import (decode_attention,
                                                paged_decode_attention)
from repro.kernels.decode_attention.ref import (decode_attention_ref,
                                                paged_decode_attention_ref)
from repro.models.model import Model, PageAllocator

pytestmark = pytest.mark.tier1

B, HQ, HKV, D, MP = 3, 4, 2, 16, 4


def _paged_case(seed: int, ps: int, T: int):
    """Random pool + bijective table + ragged lengths; every physical
    page (trash page included) is noise, so any unmasked stale read
    shows up as a mismatch against the dense oracle."""
    rng = np.random.default_rng(seed)
    pool_n = B * MP + 1
    k_pages = rng.normal(size=(pool_n, HKV, ps, D)).astype(np.float32)
    v_pages = rng.normal(size=(pool_n, HKV, ps, D)).astype(np.float32)
    table = rng.permutation(np.arange(1, pool_n)).reshape(B, MP)
    lengths = rng.integers(0, MP * ps - T + 1, size=B).astype(np.int32)
    q = rng.normal(size=(B, T, HQ, D)).astype(np.float32)
    return q, k_pages, v_pages, lengths, table.astype(np.int32)


def _gathered(pool: np.ndarray, table: np.ndarray) -> np.ndarray:
    """The dense (B, MP*ps, Hkv, D) view the gather fallback attends."""
    g = pool[table]                                   # (B, MP, Hkv, ps, D)
    return g.swapaxes(2, 3).reshape(B, -1, HKV, D)


def _all_four(q, k_pages, v_pages, lengths, table, cap):
    """(kernel, paged oracle, dense kernel, dense oracle) outputs."""
    kv = [jnp.asarray(x) for x in (q, k_pages, v_pages, lengths, table)]
    out_kernel = paged_decode_attention(*kv, logit_cap=cap, interpret=True)
    qh = kv[0].transpose(0, 2, 1, 3)
    ref_paged = paged_decode_attention_ref(
        qh, kv[1], kv[2], kv[3], kv[4], logit_cap=cap).transpose(0, 2, 1, 3)
    k_view = jnp.asarray(_gathered(k_pages, table))
    v_view = jnp.asarray(_gathered(v_pages, table))
    out_dense = decode_attention(kv[0], k_view, v_view, kv[3],
                                 logit_cap=cap, interpret=True)
    ref_dense = decode_attention_ref(
        qh, k_view.transpose(0, 2, 1, 3), v_view.transpose(0, 2, 1, 3),
        kv[3], logit_cap=cap).transpose(0, 2, 1, 3)
    return [np.asarray(o) for o in (out_kernel, ref_paged, out_dense,
                                    ref_dense)]


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([8, 16, 64]),
       st.sampled_from([1, 2, 5]), st.booleans())
def test_kernel_matches_gather_and_dense(seed, ps, T, capped):
    """Property: kernel ≡ paged oracle ≡ dense kernel ≡ dense oracle on
    random pools across page sizes, verify widths and logit caps."""
    case = _paged_case(seed, ps, T)
    outs = _all_four(*case, cap=4.0 if capped else 0.0)
    for other in outs[1:]:
        np.testing.assert_allclose(outs[0], other, rtol=2e-5, atol=2e-5)


def test_kernel_reads_forked_tables_and_masks_stale_pages():
    """Prefix-sharing shape: rows 1..B-1 alias row 0's first two pages
    (a forked table is many-to-one, not a permutation), and pages beyond
    each row's live length hold huge garbage — parity with the oracle
    plus invariance to the garbage proves the masking contract that
    makes CoW-shared pages safe to read through any row's table."""
    ps, T = 8, 2
    q, k_pages, v_pages, lengths, table = _paged_case(3, ps, T)
    table = table.copy()
    table[1:, :2] = table[0, :2]                      # forked prefix pages
    lengths = np.array([2 * ps + 3, ps + 1, 2 * ps], np.int32)

    outs = _all_four(q, k_pages, v_pages, lengths, table, cap=0.0)
    for other in outs[1:]:
        np.testing.assert_allclose(outs[0], other, rtol=2e-5, atol=2e-5)

    # poison every position past length + T - 1 (per row, via its table)
    # and the trash page; the kernel's output must not move
    pk, pv = k_pages.copy(), v_pages.copy()
    pk[0], pv[0] = 1e3, -1e3
    for b in range(B):
        first_dead = int(lengths[b]) + T
        for lp in range(MP):
            page = table[b, lp]
            lo = max(0, first_dead - lp * ps)
            if lo < ps and page not in table[0, :2]:  # keep shared live
                pk[page, :, lo:], pv[page, :, lo:] = 1e3, -1e3
    poisoned = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv),
        jnp.asarray(lengths), jnp.asarray(table), interpret=True)
    np.testing.assert_allclose(np.asarray(poisoned), outs[0],
                               rtol=2e-5, atol=2e-5)


def test_kernel_invariant_under_pool_growth():
    """grow_cache_pages pads the pool with fresh physical pages and the
    table with trash entries; neither may perturb a live row's output."""
    ps, T = 16, 3
    q, k_pages, v_pages, lengths, table = _paged_case(11, ps, T)
    before = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
        jnp.asarray(lengths), jnp.asarray(table), interpret=True)
    rng = np.random.default_rng(12)
    extra = rng.normal(size=k_pages.shape).astype(np.float32)
    grown_k = np.concatenate([k_pages, extra])
    grown_v = np.concatenate([v_pages, -extra])
    grown_tbl = np.pad(table, ((0, 0), (0, MP)))      # new entries → trash
    after = paged_decode_attention(
        jnp.asarray(q), jnp.asarray(grown_k), jnp.asarray(grown_v),
        jnp.asarray(lengths), jnp.asarray(grown_tbl), interpret=True)
    np.testing.assert_allclose(np.asarray(after), np.asarray(before),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------- token-level parity (SD)
TCFG = ModelConfig("pa-moe", "moe", 2, 128, 4, 2, 256, 512, num_experts=4,
                   num_experts_per_tok=2, dtype="float32")
SWACFG = ModelConfig("pa-swa", "dense", 2, 64, 4, 2, 128, 512,
                     layer_pattern=("attn", "swa"), sliding_window=6,
                     dtype="float32")
DCFG = ModelConfig("pa-draft", "dense", 2, 64, 2, 2, 128, 512,
                   dtype="float32")

PS, POOL_MP = 8, 4                                    # max_seq = 32


@pytest.fixture(scope="module")
def draft():
    d = Model(DCFG)
    return d, d.init(jax.random.PRNGKey(1))


def _token_trace(tcfg, draft_pair, *, paged_attention, paged, gamma,
                 rounds=4, grow_at=None):
    """Greedy committed-token trace over ``rounds`` SD rounds (fixed
    keys), optionally growing the paged pool mid-stream."""
    d, pd = draft_pair
    t = Model(tcfg, paged_attention=paged_attention)
    pt = t.init(jax.random.PRNGKey(0))
    eng = SDEngine(t, ModelProposer(t, d), gamma=max(gamma, 1))
    prompts = jnp.asarray(np.tile(np.arange(3, 9), (2, 1)))
    max_seq = POOL_MP * PS
    if paged:
        alloc = PageAllocator(2, PS, 2 * POOL_MP + 1, POOL_MP)
        for b in range(2):
            alloc.alloc(b, max_seq)
        state = eng.start(pt, pd, prompts, max_seq=max_seq,
                          key=jax.random.PRNGKey(7),
                          cache_opts={"paged": True, "page_size": PS,
                                      "pool_pages": alloc.pool_pages},
                          page_table=jnp.asarray(alloc.table))
    else:
        state = eng.start(pt, pd, prompts, max_seq=2 * max_seq,
                          key=jax.random.PRNGKey(7))
    trace = [np.asarray(state.last_token).copy()]
    for r in range(rounds):
        if paged and grow_at == r:
            state = eng.grow_session(state, 2 * max_seq,
                                     pool_pages=2 * alloc.pool_pages,
                                     max_pages=2 * POOL_MP)
            alloc.grow(2 * alloc.pool_pages, 2 * POOL_MP)
            for b in range(2):
                alloc.extend_row(b, 2 * max_seq)
            pages = dict(state.t_cache["pages"],
                         table=jnp.asarray(alloc.table))
            state = dc_replace(state,
                               t_cache=dict(state.t_cache, pages=pages))
        state, res = eng.round(state, gamma=gamma,
                               key=jax.random.PRNGKey(100 + r))
        for b in range(2):
            trace.append(res.committed[b, : res.n_commit[b]].copy())
    return trace


@pytest.mark.parametrize("gamma", [0, 1, 4])
def test_sd_rounds_token_identical_kernel_gather_dense(draft, gamma):
    """Exact greedy-token equality through whole SD rounds: the paged
    kernel, the gather fallback and a dense cache commit the SAME tokens
    at every round, for AR (gamma=0), minimal and wide speculation."""
    kernel = _token_trace(TCFG, draft, paged_attention="kernel",
                          paged=True, gamma=gamma)
    gather = _token_trace(TCFG, draft, paged_attention="gather",
                          paged=True, gamma=gamma)
    dense = _token_trace(TCFG, draft, paged_attention="kernel",
                         paged=False, gamma=gamma)
    for a, b, c in zip(kernel, gather, dense):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)


def test_sd_rounds_token_identical_swa_rows(draft):
    """Mixed attn+swa stacks: SWA layers keep their dense ring rows in a
    paged cache (they never enter the kernel), attn layers take the
    kernel — tokens still match gather and dense exactly."""
    kernel = _token_trace(SWACFG, draft, paged_attention="kernel",
                          paged=True, gamma=2)
    gather = _token_trace(SWACFG, draft, paged_attention="gather",
                          paged=True, gamma=2)
    dense = _token_trace(SWACFG, draft, paged_attention="kernel",
                         paged=False, gamma=2)
    for a, b, c in zip(kernel, gather, dense):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)


def test_sd_rounds_token_identical_across_growth(draft):
    """Mid-stream pool growth (grow_session + allocator extend): the
    grown kernel session stays token-identical to the grown gather
    session AND to a dense session sized for the final capacity."""
    kernel = _token_trace(TCFG, draft, paged_attention="kernel",
                          paged=True, gamma=2, rounds=6, grow_at=3)
    gather = _token_trace(TCFG, draft, paged_attention="gather",
                          paged=True, gamma=2, rounds=6, grow_at=3)
    dense = _token_trace(TCFG, draft, paged_attention="kernel",
                         paged=False, gamma=2, rounds=6)
    for a, b, c in zip(kernel, gather, dense):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)
