"""Batched speculative-decoding engine (the paper's serving mechanism).

One SD round (Sec. 3.1), generic over any registered Proposer
(core/proposer.py):

  1. PROPOSE  — ``proposer.propose`` emits g <= gamma draft tokens per
     sequence with their draft distributions (a small model, an EAGLE
     head, or nothing at all for the AR baseline).
  2. VERIFY   — the target model processes [last_token, d_1..d_g]
     (g+1 tokens) in ONE forward, yielding g+1 next-token distributions.
  3. REJECT   — batched rejection sampling (rejection.py) accepts a per-
     sequence prefix of the drafts and emits one extra token (residual
     sample or bonus).  n_commit = n_accept + 1 ∈ [1, g+1].
  4. COMMIT   — target cache commit + ``proposer.commit`` reconcile both
     sides to the accepted prefix.

The AR baseline is the degenerate g=0 instance of the SAME loop (the
"none" proposer): the round collapses to one target forward of
``last_token`` plus a sample — so SD and AR timings come from identical
machinery, which is what the paper's speedup definition x = T_AR/T_SD
requires.

Session/round API (the continuous-batching seam):
  * ``start(params_t, params_p, prompts, max_seq=...)`` → ``SessionState``
    (target prefill + cache alloc + proposer state; the prefill-sampled
    token is the first generated token and lives in ``state.last_token``).
  * ``round(state, gamma=..., key=..., active=...)`` →
    ``(SessionState, RoundResult)`` — ONE propose/verify/reject/commit
    round.  ``active`` is a (B,) bool mask: inactive rows commit zero
    tokens (``lengths`` frozen, ``last_token`` unchanged), so a caller can
    retire finished sequences without changing the compiled shape.
  * ``admit(state, prompts, lengths, admit_mask)`` → ``SessionState`` —
    masked prefill of NEW requests into retired rows of a live session:
    the full bucket is prefilled into fresh caches and merged row-wise
    (models/model.merge_cache_rows + Proposer.merge_state), so occupancy
    changes within a batch bucket cause zero round retraces.  Its cost is
    ∝ the POOL (non-admitted rows are prefilled and discarded).
  * ``admit_rows(state, prompts, lengths, rows)`` → ``SessionState`` —
    the row-SLICED admission path: only the R admitted rows are prefilled,
    at their own (R, prompt-bucket) shape, and the fresh KV/proposer state
    is row-scattered into the live session (models/model.
    scatter_cache_rows + Proposer.scatter_state).  Admission cost scales
    with what was admitted, not the pool.
  * ``begin_admit_chunked``/``admit_chunk`` — the sliced path split into
    fixed-size prompt chunks so a long-prompt admission interleaves with
    decode rounds instead of stalling the round it lands in.
  * ``grow_session(state, new_max_seq, ...)`` — pad a paged session's
    logical capacity (and the proposer's dense caches) so late-arriving
    long requests admit instead of crashing the stream.
  * ``generate(...)`` is kept as the thin start+round loop for parity.

The caller owning the loop is what enables continuous batching
(serving/scheduler.py): slots retire on completion, new requests prefill
into freed rows between rounds, and {use_sd, gamma} can be re-planned on
the LIVE batch size every round — the paper's N(t)-dependence operated,
not just measured.

Cache discipline:
  * target/draft attention KV: fresh tokens are written at offsets
    ``lengths``; a rejected suffix is simply left stale (masked by
    position) and ``lengths += n_commit``.  Retired rows' stale entries
    are likewise harmless: every extend writes its positions before
    attending, so a re-admitted row overwrites exactly the entries that
    become visible.
  * recurrent states (SSM/xLSTM targets or drafts): verify collects
    per-step states and ``commit`` gathers the state of the last accepted
    token (models/model.py).  Recurrent drafts re-run the verify pass from
    a pre-round snapshot (γ+1 cheap draft tokens) since their propose loop
    advances state destructively.  A retired (inactive) row's recurrent
    state is garbage until re-admission rebuilds it.

Compile caching: each SDEngine instance is a long-lived *decoding
session*.  Per gamma it builds the fused round once (``_round_cache``)
and jax.jit then caches per batch/sequence shape; ``trace_log`` records
every (gamma, batch) retrace and ``admit_trace_log`` every admission
retrace, so serving code (and tests) can assert reuse.  The engine never
mixes tokens across sequences — per-sequence lengths make the batch
ragged, exactly like continuous batching in vLLM.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.proposer import Proposer, make_proposer
from repro.core.rejection import probs_from_logits, rejection_sample, sample_from
from repro.distributed.constraints import resolve_mesh
from repro.distributed.sharding import shard_cache
from repro.models.model import Model
from repro.models.moe import warm_experts as moe_warm_experts
from repro.serving.faults import logits_finite


def _device_cast(x, np_dtype):
    """Host-boundary dtype cast: convert in numpy FIRST so the transfer is
    a pure device_put.  ``jnp.asarray(host_array, jnp.int32)`` instead
    compiles a tiny convert_element_type program per shape — visible as a
    spurious XLA compile under ``repro.analysis.compile_guard`` at every
    new admission bucket.  Device arrays pass through untouched (a numpy
    round-trip would force a sync)."""
    if isinstance(x, jax.Array):
        return x
    # lint: allow[T104] tracers are jax.Array and return early above; only host values reach here
    return jnp.asarray(np.asarray(x, np_dtype))


@dataclass
class SDStats:
    rounds: int = 0
    generated: int = 0                      # total committed tokens (all seqs)
    max_possible: int = 0                   # rounds * (gamma+1) * B_live
    accept_events: int = 0                  # accepted draft tokens
    draft_events: int = 0                   # proposed draft tokens
    round_time: float = 0.0                 # wall time across all rounds
    propose_time: float = 0.0               # per-phase (timed=True only)
    verify_time: float = 0.0
    reject_time: float = 0.0
    # expert-prefetch accounting (prefetch-aware proposers only): summed
    # over rounds, layers and periods — hits = activated AND warmed
    prefetch_hits: int = 0
    prefetch_actual: int = 0                # experts the verify passes hit
    prefetch_predicted: int = 0             # experts the plans warmed
    warm_time: float = 0.0                  # warm DISPATCH time (timed only)

    @property
    def sigma(self) -> float:               # paper's σ (Eq. 5 empirical)
        return self.generated / max(self.max_possible, 1)

    @property
    def alpha(self) -> float:               # empirical acceptance rate
        return self.accept_events / max(self.draft_events, 1)

    @property
    def prefetch_misses(self) -> int:       # activated but NOT warmed
        return self.prefetch_actual - self.prefetch_hits

    @property
    def prefetch_hit_rate(self) -> float:   # P(activated expert was warm)
        return self.prefetch_hits / max(self.prefetch_actual, 1)

    def absorb_round(self, res: "RoundResult", live: int) -> None:
        """Fold one RoundResult into the aggregate.

        ``live`` is the number of rows the round was REQUESTED to advance
        (the active count; masked-out lanes commit nothing) — sigma/alpha
        are accounted against it, and against the requested gamma, so a
        proposer drafting fewer than gamma tokens honestly scores
        sigma < 1.  Shared by wave ``generate`` and the continuous
        scheduler so the two schedulers can never diverge in bookkeeping.
        """
        self.rounds += 1
        self.round_time += res.round_time
        if res.phase_times:
            self.propose_time += res.phase_times.get("propose", 0.0)
            self.verify_time += res.phase_times.get("verify", 0.0)
            self.reject_time += res.phase_times.get("reject", 0.0)
            self.warm_time += res.phase_times.get("warm", 0.0)
        self.generated += int(res.n_commit.sum())
        self.max_possible += (res.gamma + 1) * live
        self.accept_events += int(res.n_accept.sum())
        self.draft_events += res.width * live
        if res.pf is not None:
            self.prefetch_hits += res.pf["hits"]
            self.prefetch_actual += res.pf["actual"]
            self.prefetch_predicted += res.pf["predicted"]


@dataclass
class SessionState:
    """One live decoding batch: everything a round reads and writes.

    ``params`` is the ``{"target": ..., "draft": ...}`` dict,
    ``t_cache``/``p_state`` the target cache and proposer state,
    ``last_token`` (B,) the most recently committed token per row (after
    ``start``/``admit`` it holds the prefill-sampled FIRST generated token
    of each fresh row — the caller records it as output).  ``max_seq`` is
    the static cache capacity the state was allocated with.
    """
    params: dict
    t_cache: dict
    p_state: Any
    last_token: jnp.ndarray
    max_seq: int

    @property
    def batch(self) -> int:
        return int(self.last_token.shape[0])


@dataclass
class RoundResult:
    """Host-side outcome of one SD round.

    ``committed`` is (B, width+1); per row only the first ``n_commit[b]``
    entries are real (0 for rows that were inactive this round).
    ``n_accept`` is per-row accepted draft tokens; ``width`` the drafted
    tokens per sequence (g <= gamma); ``pf`` the prefetch hit/actual/
    predicted counts (prefetch-aware proposers, else None);
    ``phase_times`` the propose/verify/reject/warm wall times (timed
    rounds only, else None).  ``finite`` (B,) is the numerical
    sentinel's verdict on this round's raw verify logits
    (serving/faults.logits_finite): a False row committed NOTHING this
    round (quarantined inside ``finalize``) and should be retired by the
    caller with ``finish_reason="numerical_fault"``.

    ``round_time`` runs from the round's dispatch to the end of its one
    device fetch; ``dispatch_time`` from the call's entry (key split and
    ``active`` upload included) until the round's programs are dispatched
    — host time the device may sit idle through.  ``experts_reached`` is
    the distinct experts the verify's routing reached in each target MoE
    layer, summed over the layers (``Model.verify``; the experts whose
    weights the ragged gmm reads), or None when the target does not count
    them (dense, or the "ep" dispatch).
    """
    committed: np.ndarray
    n_commit: np.ndarray
    n_accept: np.ndarray
    width: int
    gamma: int
    pf: Optional[Dict[str, int]]
    round_time: float
    phase_times: Optional[Dict[str, float]] = None
    finite: Optional[np.ndarray] = None
    dispatch_time: float = 0.0
    experts_reached: Optional[int] = None


@dataclass
class PendingAdmission:
    """A chunked sliced admission in flight (SDEngine.begin_admit_chunked).

    ``t_cache`` is the compact DENSE target cache under construction
    (None until the first chunk ran), ``consumed`` the prompt tokens
    prefilled so far.  The admitted rows join the live session only when
    ``admit_chunk`` returns ``None`` for the pending half.
    """
    prompts: np.ndarray                  # (R, Tp) host-side
    lengths: np.ndarray                  # (R,) true prompt lengths (equal)
    rows: np.ndarray                     # (R,) destination pool rows
    chunk: int
    key: jax.Array
    t_cache: Optional[dict] = None
    consumed: int = 0

    @property
    def remaining(self) -> int:
        return int(self.lengths[0]) - self.consumed


class SDEngine:
    """One persistent decoding session: a target model + one Proposer.

    The propose/verify/reject/commit round is generic over the proposer;
    compiled rounds are cached per gamma (and, via jit, per shape), so a
    serving engine can hold one SDEngine per proposer kind and change
    gamma between waves — or per ROUND, via the ``start``/``round``/
    ``admit`` session API — without rebuilding anything.
    """

    def __init__(self, target: Model, proposer: Proposer, *,
                 gamma: int = 4, temperature: float = 0.0,
                 mesh=None, mesh_layout: Optional[str] = None):
        self.target = target
        self.proposer = proposer
        self.gamma = gamma
        self.temperature = temperature
        # mesh defaults to the target model's (one mesh per session);
        # host-boundary inputs are then committed REPLICATED so every jit
        # call sees one placement signature (docs/distributed.md), and
        # session caches open device_put per distributed.sharding.cache_spec
        if mesh is None and getattr(target, "mesh", None) is not None:
            mesh = target.mesh
            mesh_layout = (mesh_layout if mesh_layout is not None
                           else target.mesh_layout)
        if mesh is not None:
            mesh, mesh_layout = resolve_mesh(mesh, mesh_layout)
        self.mesh = mesh
        self.mesh_layout = mesh_layout
        self._replicated = (NamedSharding(mesh, PartitionSpec())
                            if mesh is not None else None)
        self._greedy_key = None      # cached PRNGKey(0) for greedy rounds
        self._round_cache: Dict[int, Callable] = {}      # gamma -> jitted round
        self._stage_cache: Dict[int, Tuple] = {}         # gamma -> stage jits
        self._admit_cache: Dict[Tuple[int, int, int], Callable] = {}
        self._sliced_cache: Dict[Tuple[int, int, int], Callable] = {}
        self._chunk_cache: Dict[Tuple, Callable] = {}
        self._start_cache: Dict[Tuple, Callable] = {}    # session-open prefill
        self._prefix_cache: Dict[Tuple[int, int, int, int], Callable] = {}
        self.trace_log: List[Tuple[int, int]] = []       # (gamma, B) per trace
        # (T_prompt, rows): full-path entries carry rows == pool, sliced-
        # path entries rows == the admitted-row bucket — the jit-signature
        # contract tests assert on
        self.admit_trace_log: List[Tuple[int, int]] = []
        self.chunk_trace_log: List[Tuple[str, int, int]] = []  # (stage, C, R)
        # (T_tail, rows) per prefix-admission trace — the shared-prefix
        # counterpart of admit_trace_log
        self.prefix_trace_log: List[Tuple[int, int]] = []
        self.growth_log: List[Tuple[int, Optional[int]]] = []
        # session-lifetime expert-prefetch aggregates (prefetch proposers):
        # summed across every generate() call this session served
        self.prefetch_totals: Dict[str, int] = {
            "hits": 0, "actual": 0, "predicted": 0, "rounds": 0}

    def _host(self, x, np_dtype):
        """Host-boundary cast with mesh-aware placement: under a mesh every
        host value is committed REPLICATED, so repeated calls (new streams,
        new admission waves) present identical sharding signatures to the
        jit caches — an uncommitted single-device array next to sharded
        params would otherwise key (and retrace) on whatever placement the
        first call happened to see."""
        if self._replicated is not None and not isinstance(x, jax.Array):
            return jax.device_put(np.asarray(x, np_dtype), self._replicated)  # lint: allow[T104] tracers are jax.Array and take the _device_cast branch; only host values reach here
        return _device_cast(x, np_dtype)

    def _constrain_cache(self, t_cache):
        """In-graph placement pin for the session cache under a mesh.

        Every jitted program that RETURNS the session cache constrains it
        to the distributed.sharding.cache_spec placement the session
        opened with.  Without the pin XLA propagates its own output
        shardings (e.g. paged pools re-split over the kv-head/head dims),
        so a round compiled after an admission sees differently-sharded
        cache inputs than one compiled after a round — two live
        specializations of every program for one logical stream, which is
        exactly what the runtime ``sharding_guard`` flags.
        """
        if self.mesh is None:
            return t_cache
        from repro.distributed.sharding import shard_cache
        return jax.lax.with_sharding_constraint(
            t_cache, shard_cache(t_cache, self.mesh))

    def compiled_gammas(self) -> List[int]:
        """Gammas with a built round (fused or staged) in this session."""
        return sorted(set(self._round_cache) | set(self._stage_cache))

    def accumulate_prefetch_totals(self, stats: SDStats) -> None:
        """Fold one generation/stream's prefetch counts into the
        session-lifetime totals (no-op for non-prefetch proposers)."""
        if getattr(self.proposer, "provides_prefetch", False):
            self.prefetch_totals["hits"] += stats.prefetch_hits
            self.prefetch_totals["actual"] += stats.prefetch_actual
            self.prefetch_totals["predicted"] += stats.prefetch_predicted
            self.prefetch_totals["rounds"] += stats.rounds

    # ----------------------------------------------------------- round pieces
    def _stages(self, gamma: int):
        """(propose, verify, finalize) pure stage functions for one gamma.

        Verify runs ``Model.verify`` and returns, besides the target
        distributions, the experts its routing reached (None when the
        target does not count them).  Prefetch-aware proposers
        (``provides_prefetch``) pass the round's ``PrefetchPlan`` too and
        get its hit/miss counts back as ``pf``; all other proposers' verify
        returns ``pf = None``.
        """
        target, proposer, temp = self.target, self.proposer, self.temperature
        pf_aware = getattr(proposer, "provides_prefetch", False)

        def propose(params, p_state, last_token, k_prop):
            return proposer.propose(params, p_state, last_token, gamma, k_prop)

        # the numerical sentinel reads RAW verify logits: the greedy
        # probs_from_logits branch is one_hot(argmax), and argmax of an
        # all-NaN row returns a valid index — probabilities hide faults
        def verify(params_t, t_cache, last_token, drafts, plan=None):
            verify_tokens = jnp.concatenate([last_token[:, None], drafts], 1)
            logits, hidden, pend, counts = target.verify(
                params_t, verify_tokens, t_cache, plan=plan, collect=True)
            if not proposer.needs_hidden:
                hidden = None
            pf = None
            if pf_aware:
                pf = {k: counts[k] for k in ("hits", "actual", "predicted")}
            return (probs_from_logits(logits, temp), hidden, pend, pf,
                    logits_finite(logits), counts.get("experts_reached"))

        def finalize(params, pend, p_state, base_len, p_dist, q_dist, drafts,
                     hidden, last_token, active, finite, k_rej):
            B, g = drafts.shape
            n_accept, next_token, _ = rejection_sample(
                p_dist, q_dist, drafts, k_rej, temp)
            # inactive (retired) rows commit nothing: lengths stay frozen
            # and last_token is carried over, so the row is shape-stable
            # padding until admit() refills it.  Non-finite rows are
            # quarantined the same way — zero commits keep the fault out
            # of the caches and out of co-batched rows' bookkeeping; the
            # scheduler reads RoundResult.finite and retires them.
            ok = jnp.logical_and(active, finite)
            n_accept = jnp.where(ok, n_accept, 0)
            n_commit = jnp.where(ok, n_accept + 1, 0)
            t_cache = target.commit(pend, n_commit, collected=True)
            verify_tokens = jnp.concatenate([last_token[:, None], drafts], 1)
            p_state = proposer.commit(
                params, p_state, base_len=base_len, n_accept=n_accept,
                n_commit=n_commit, verify_tokens=verify_tokens, hidden=hidden)
            # committed new tokens this round: [d_1..d_n, next] (n_commit each)
            slot = jnp.arange(g + 1)[None, :]
            drafts_pad = jnp.concatenate(
                [drafts, jnp.zeros((B, 1), drafts.dtype)], 1)
            committed = jnp.where(slot < n_accept[:, None], drafts_pad,
                                  next_token[:, None])          # (B, g+1)
            new_last = jnp.where(ok, next_token, last_token)
            return (t_cache, p_state, new_last, committed, n_commit, n_accept)

        return propose, verify, finalize

    def _round_fn(self, gamma: int) -> Callable:
        """Fused jitted round for one gamma (built once per session).

        Prefetch-aware proposers never take this path — inside one
        monolithic XLA computation the warm gather would be dead code, so
        rounds always run them staged (see ``_staged_jits``).
        """
        if getattr(self.proposer, "provides_prefetch", False):
            raise RuntimeError(
                "prefetch-aware proposers decode through staged rounds; "
                "the fused round cannot express the warm dispatch")
        fn = self._round_cache.get(gamma)
        if fn is None:
            propose, verify, finalize = self._stages(gamma)

            def round_fn(params, t_cache, p_state, last_token, active,
                         k_prop, k_rej):
                # trace-time side effect: lets callers assert compile reuse
                self.trace_log.append((gamma, int(last_token.shape[0])))  # lint: allow[T106] intentional trace-time counter; tier-1 tests assert on it
                base_len = t_cache["lengths"]
                drafts, q_dist, p_work = propose(params, p_state, last_token,
                                                 k_prop)
                p_dist, hidden, pend, pf, finite, reached = verify(
                    params["target"], t_cache, last_token, drafts)
                out = finalize(params, pend, p_work, base_len, p_dist,
                               q_dist, drafts, hidden, last_token, active,
                               finite, k_rej)
                out = (self._constrain_cache(out[0]),) + out[1:]
                return out + (finite, pf, reached)

            fn = jax.jit(round_fn)
            self._round_cache[gamma] = fn
        return fn

    def _staged_jits(self, gamma: int):
        """Separately-jitted (propose, verify, finalize, warm) stages.

        Used for ``timed=True`` (syncing between stages gives real per-phase
        wall times) and for prefetch-aware proposers even untimed: the round
        must be split so the host can dispatch the expert-warm gather
        *between* the propose and verify launches — that interleaving is the
        overlap (a fused round gives XLA one monolithic computation and the
        warm gather would be dead code).  ``warm`` is ``None`` for ordinary
        proposers.
        """
        fns = self._stage_cache.get(gamma)
        if fns is None:
            propose, verify, finalize = self._stages(gamma)

            def propose_logged(params, p_state, last_token, k_prop):
                self.trace_log.append((gamma, int(last_token.shape[0])))  # lint: allow[T106] intentional trace-time counter; tier-1 tests assert on it
                return propose(params, p_state, last_token, k_prop)

            warm = None
            if getattr(self.proposer, "provides_prefetch", False):
                target_cfg = self.target.cfg
                warm_mesh = self.mesh

                def warm(params_t, plan):
                    # mesh threaded → each shard gathers only ITS expert
                    # slice (models/moe.warm_experts shard_map path)
                    return moe_warm_experts(params_t["layers"], target_cfg,
                                            plan, mesh=warm_mesh)
                warm = jax.jit(warm)

            def finalize_pinned(*a):
                out = finalize(*a)
                return (self._constrain_cache(out[0]),) + out[1:]

            fns = (jax.jit(propose_logged), jax.jit(verify),
                   jax.jit(finalize_pinned), warm)
            self._stage_cache[gamma] = fns
        return fns

    # --------------------------------------------------------------- prefill
    def prefill(self, params_t, params_p, prompts: jnp.ndarray, max_seq: int,
                *, lengths=None, key=None,
                prefill_kwargs: Optional[dict] = None,
                cache_opts: Optional[dict] = None, page_table=None):
        """Prefill target + proposer; returns (t_cache, p_state, last_token).

        ``cache_opts`` forwards to ``Model.init_cache`` (e.g.
        ``{"paged": True, "page_size": 64, "pool_pages": N}``);
        ``page_table`` pre-assigns the paged cache's block table (a
        ``PageAllocator``'s table) so the prefill writes land in the
        admitted rows' pages.  Proposer caches stay dense either way.

        The common path (no ``prefill_kwargs``) runs through a jitted
        session-open program cached per ``(max_seq, cache_opts)`` — jax
        then caches per shape, so re-opening a session for a new stream
        of a warm shape compiles NOTHING (eager execution instead paid a
        full prefill-scan recompile per stream; the retrace guard in
        tests/test_retrace_guard.py pins this).  Exotic prefill kwargs
        (encoder embeds, mrope positions, ...) fall back to the eager
        path rather than guessing their static/traced split."""
        params = {"target": params_t, "draft": params_p}
        key = key if key is not None else jax.random.PRNGKey(0)
        if not prefill_kwargs:
            fn = self._start_fn(max_seq, cache_opts)
            return fn(params, self._host(prompts, np.int32),
                      None if lengths is None
                      else self._host(lengths, np.int32),
                      None if page_table is None
                      else self._host(page_table, np.int32), key)
        t_cache, p_state, last_l = self._fresh_prefill(
            params, prompts, lengths, max_seq, cache_opts=cache_opts,
            page_table=page_table, prefill_kwargs=prefill_kwargs)
        p = probs_from_logits(last_l, self.temperature)
        last_token = sample_from(p, key, self.temperature)
        return t_cache, p_state, last_token

    def _start_fn(self, max_seq: int, cache_opts: Optional[dict]) -> Callable:
        opts_key = (None if not cache_opts
                    else tuple(sorted(cache_opts.items())))
        fn = self._start_cache.get((max_seq, opts_key))
        if fn is None:
            opts = dict(cache_opts) if cache_opts else None

            def start_fn(params, prompts, lengths, page_table, key):
                t_cache, p_state, last_l = self._fresh_prefill(
                    params, prompts, lengths, max_seq, cache_opts=opts,
                    page_table=page_table)
                p = probs_from_logits(last_l, self.temperature)
                return (self._constrain_cache(t_cache), p_state,
                        sample_from(p, key, self.temperature))

            fn = jax.jit(start_fn)
            self._start_cache[(max_seq, opts_key)] = fn
        return fn

    # --------------------------------------------------------------- session
    def start(self, params_t, params_p, prompts: jnp.ndarray, *,
              max_seq: int, lengths=None, key=None,
              prefill_kwargs: Optional[dict] = None,
              cache_opts: Optional[dict] = None,
              page_table=None) -> SessionState:
        """Open a decoding batch: prefill + cache alloc → ``SessionState``.

        The prefill-sampled token is each row's FIRST generated token; the
        caller reads it from ``state.last_token``.  ``max_seq`` is the
        static cache capacity for the whole batch lifetime — unless the
        session is PAGED (``cache_opts={"paged": True, ...}`` +
        ``page_table``), where it is only the initial logical capacity and
        ``grow_session`` raises it later without resizing any row.
        """
        t_cache, p_state, last_token = self.prefill(
            params_t, params_p, prompts, max_seq, lengths=lengths, key=key,
            prefill_kwargs=prefill_kwargs, cache_opts=cache_opts,
            page_table=page_table)
        if self.mesh is not None:
            # place the session cache per distributed.sharding.cache_spec
            # ONCE at open (batch over data axes, KV heads / page pools
            # over "model"); rounds then carry the placement forward
            t_cache = jax.device_put(t_cache,
                                     shard_cache(t_cache, self.mesh))
        return SessionState(params={"target": params_t, "draft": params_p},
                            t_cache=t_cache, p_state=p_state,
                            last_token=last_token, max_seq=max_seq)

    def round(self, state: SessionState, *, gamma: Optional[int] = None,
              key: Optional[jax.Array] = None, active=None,
              timed: bool = False) -> Tuple[SessionState, RoundResult]:
        """Run ONE propose/verify/reject/commit round on a live session.

        Parameters
        ----------
        state : SessionState
            From ``start``/``admit``/the previous ``round``.
        gamma : int, optional
            Speculation width for THIS round (default: the session's).
            gamma=0 is the in-session AR fallback: zero drafts, one target
            forward — the SD→AR handoff needs no session switch.
        key : jax.Array, optional
            Round PRNG key (split internally into propose/reject keys).
        active : array-like, optional
            (B,) bool — rows to advance.  Inactive rows commit 0 tokens and
            keep ``lengths``/``last_token`` frozen; the mask is data, so
            occupancy changes never retrace.  Default: all rows active.
        timed : bool
            Run staged with per-phase syncs (fills ``phase_times``).

        Returns
        -------
        (SessionState, RoundResult)
            The advanced state and the round's host-side outcome.
        """
        t_entry = time.perf_counter()
        with jax.profiler.TraceAnnotation("sd.round"):
            return self._round(state, gamma, key, active, timed, t_entry)

    def _round(self, state: SessionState, gamma: Optional[int], key,
               active, timed: bool, t_entry: float
               ) -> Tuple[SessionState, RoundResult]:
        """``round``'s body, in three profiler spans: ``sd.round.prepare``
        (keys, ``active`` upload), ``sd.round.dispatch`` (the round's
        programs dispatched) and ``sd.round.fetch`` (the one device_get)."""
        with jax.profiler.TraceAnnotation("sd.round.prepare"):
            gamma = self.gamma if gamma is None else gamma
            if key is None:
                # greedy rounds are key-independent; at temperature>0 a
                # fixed default would silently reuse IDENTICAL propose/
                # reject noise every round of the caller's loop — fail
                # loudly instead
                if self.temperature > 0.0:
                    raise ValueError(
                        "round() needs a fresh per-round key at "
                        "temperature>0 (split one from a root key each "
                        "round)")
                # built once: a fresh PRNGKey here would be one implicit
                # host-to-device transfer per round (transfer_guard counts
                # it)
                if self._greedy_key is None:
                    self._greedy_key = jax.random.PRNGKey(0)
                key = self._greedy_key
            k_prop, k_rej = jax.random.split(key)
            B = state.batch
            active = self._host(np.ones((B,), bool) if active is None
                                else active, bool)
        params = state.params
        pf_aware = getattr(self.proposer, "provides_prefetch", False)
        staged = timed or pf_aware
        phases: Dict[str, float] = {}
        t_round = time.perf_counter()
        with jax.profiler.TraceAnnotation("sd.round.dispatch"):
            if staged:
                (t_cache, p_state, last_token, committed, n_commit, n_acc,
                 finite, pf, reached) = self._staged_round(
                    state, gamma, active, k_prop, k_rej, timed, phases)
            else:
                fn = self._round_fn(gamma)
                (t_cache, p_state, last_token, committed, n_commit, n_acc,
                 finite, pf, reached) = fn(params, state.t_cache,
                                           state.p_state, state.last_token,
                                           active, k_prop, k_rej)
        dispatch_time = time.perf_counter() - t_entry
        # the round's one device sync: an explicit fetch of everything the
        # host reads (np.asarray on each would be five implicit transfers)
        with jax.profiler.TraceAnnotation("sd.round.fetch"):
            committed, n_commit_np, n_acc, finite, pf, reached = \
                jax.device_get((committed, n_commit, n_acc, finite, pf,
                                reached))
        round_time = time.perf_counter() - t_round
        pf_counts = None
        if pf is not None:
            pf_counts = {k: int(pf[k]) for k in ("hits", "actual", "predicted")}
        new_state = replace(state, t_cache=t_cache, p_state=p_state,
                            last_token=last_token)
        result = RoundResult(
            committed=committed, n_commit=n_commit_np,
            n_accept=n_acc, width=committed.shape[1] - 1,
            gamma=gamma, pf=pf_counts, round_time=round_time,
            phase_times=phases if timed else None,
            finite=finite, dispatch_time=dispatch_time,
            experts_reached=None if reached is None else int(reached))
        return new_state, result

    def _staged_round(self, state: SessionState, gamma: int, active, k_prop,
                      k_rej, timed: bool, phases: Dict[str, float]):
        """One round through the separately-jitted stages (``timed`` rounds
        block after each stage and record its time in ``phases``); returns
        the fused round's outputs."""
        j_prop, j_verify, j_fin, j_warm = self._staged_jits(gamma)
        params = state.params
        t_cache, p_state, last_token = (state.t_cache, state.p_state,
                                        state.last_token)
        base_len = t_cache["lengths"]
        pf_aware = getattr(self.proposer, "provides_prefetch", False)
        t0 = time.perf_counter()
        drafts, q_dist, p_work = j_prop(params, p_state, last_token, k_prop)
        if timed:
            jax.block_until_ready(drafts)
            phases["propose"] = time.perf_counter() - t0
        if j_warm is not None:
            # async dispatch, never blocked on: the gather of the predicted
            # experts' weights runs ahead of verify on the device queue
            # while the host assembles the verify call
            t0 = time.perf_counter()
            j_warm(params["target"], p_work["plan"])
            if timed:
                # timed-only, like the other phase stats (and like them
                # the first round includes trace+compile)
                phases["warm"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        plan = p_work["plan"] if pf_aware else None
        p_dist, hidden, pend, pf, finite, reached = j_verify(
            params["target"], t_cache, last_token, drafts, plan)
        if timed:
            jax.block_until_ready(p_dist)
            phases["verify"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = j_fin(params, pend, p_work, base_len, p_dist, q_dist, drafts,
                    hidden, last_token, active, finite, k_rej)
        if timed:
            jax.block_until_ready(out[3])
            phases["reject"] = time.perf_counter() - t0
        return out + (finite, pf, reached)

    # -------------------------------------------------------------- admission
    def _admit_fn(self, B: int, Tp: int, max_seq: int) -> Callable:
        fn = self._admit_cache.get((B, Tp, max_seq))
        if fn is None:
            target, proposer, temp = self.target, self.proposer, \
                self.temperature

            def admit_fn(params, t_cache, p_state, last_token, prompts,
                         lengths, mask, key):
                self.admit_trace_log.append((Tp, B))  # lint: allow[T106] intentional trace-time counter; tier-1 tests assert on it
                fresh_t = target.init_cache(B, max_seq)
                if proposer.needs_hidden:
                    last_l, last_h, fresh_t = target.prefill_with_hidden(
                        params["target"], prompts, fresh_t, lengths=lengths)
                else:
                    last_l, fresh_t = target.prefill(
                        params["target"], prompts, fresh_t, lengths=lengths)
                    last_h = None
                fresh_p = proposer.init_state(params, prompts, max_seq,
                                              lengths=lengths,
                                              last_hidden=last_h)
                first = sample_from(probs_from_logits(last_l, temp), key,
                                    temp)
                from repro.models.model import merge_cache_rows
                merged_t = self._constrain_cache(
                    merge_cache_rows(t_cache, fresh_t, mask))
                merged_p = proposer.merge_state(p_state, fresh_p, mask)
                merged_last = jnp.where(mask, first, last_token)
                return merged_t, merged_p, merged_last

            fn = jax.jit(admit_fn)
            self._admit_cache[(B, Tp, max_seq)] = fn
        return fn

    def admit(self, state: SessionState, prompts: jnp.ndarray, lengths,
              admit_mask, *, key: Optional[jax.Array] = None
              ) -> SessionState:
        """Masked prefill of new requests into retired rows of a session.

        The full (B, T_prompt) bucket is prefilled into FRESH target/
        proposer caches and the result is merged row-wise with the live
        state: rows where ``admit_mask`` is True take the fresh prefill,
        all other rows keep their in-flight cache untouched.  The mask is
        data, so WHICH rows get admitted never retraces — only a new
        (batch, prompt-bucket) shape does (logged in ``admit_trace_log``).

        Parameters
        ----------
        state : SessionState
            The live session (from ``start``/``round``).
        prompts : jnp.ndarray
            (B, T_prompt) tokens.  Admitted rows carry the new prompts;
            non-admitted rows are don't-care fillers (their prefill is
            computed and discarded — the price of a static shape).
        lengths : array-like
            (B,) true prompt lengths (>= 1 everywhere, fillers included).
        admit_mask : array-like
            (B,) bool — True rows are (re)initialised.
        key : jax.Array, optional
            PRNG key for the admitted rows' first sampled token (read it
            from ``state.last_token`` after this call).

        Returns
        -------
        SessionState
            The merged state; admitted rows are prefilled to their prompt
            and ready for the next ``round``.
        """
        B, Tp = prompts.shape
        if B != state.batch:
            raise ValueError(f"admit batch {B} != session batch "
                             f"{state.batch}")
        key = key if key is not None else jax.random.PRNGKey(0)
        mask = self._host(admit_mask, bool)
        fn = self._admit_fn(B, Tp, state.max_seq)
        t_cache, p_state, last_token = fn(
            state.params, state.t_cache, state.p_state, state.last_token,
            self._host(prompts, np.int32), self._host(lengths, np.int32),
            mask, key)
        return replace(state, t_cache=t_cache, p_state=p_state,
                       last_token=last_token)

    # ------------------------------------------------------ sliced admission
    def _fresh_prefill(self, params, prompts, lengths, max_seq, *,
                       cache_opts=None, page_table=None,
                       prefill_kwargs=None):
        """Prefill a batch into fresh caches + proposer state; returns
        (t_cache, p_state, last_logits).  The one shared implementation
        behind ``prefill``/``start`` (full batch, optionally paged), the
        sliced ``admit_rows`` path (compact R-row dense batch) and the
        final chunk of a chunked admission."""
        target, proposer = self.target, self.proposer
        kw = prefill_kwargs or {}
        B = prompts.shape[0]
        fresh_t = target.init_cache(B, max_seq, **(cache_opts or {}))
        if page_table is not None:
            fresh_t["pages"] = dict(fresh_t["pages"],
                                    table=jnp.asarray(page_table, jnp.int32))
        if proposer.needs_hidden:
            last_l, last_h, fresh_t = target.prefill_with_hidden(
                params["target"], prompts, fresh_t, lengths=lengths, **kw)
        else:
            last_l, fresh_t = target.prefill(
                params["target"], prompts, fresh_t, lengths=lengths, **kw)
            last_h = None
        fresh_p = proposer.init_state(params, prompts, max_seq,
                                      lengths=lengths, last_hidden=last_h)
        return fresh_t, fresh_p, last_l

    def _scatter_admitted(self, state_parts, fresh, rows, valid, key, Tp):
        """Scatter a compact fresh (cache, p_state, last_logits) into the
        live session arrays; shared by admit_rows and the final chunk."""
        from repro.models.model import scatter_cache_rows
        t_cache, p_state, last_token = state_parts
        fresh_t, fresh_p, last_l = fresh
        first = sample_from(probs_from_logits(last_l, self.temperature), key,
                            self.temperature)
        merged_t = self._constrain_cache(
            scatter_cache_rows(t_cache, fresh_t, rows, valid=valid,
                               n_prompt=Tp))
        merged_p = self.proposer.scatter_state(p_state, fresh_p, rows,
                                               valid=valid)
        B = last_token.shape[0]
        rows_eff = jnp.where(valid, jnp.asarray(rows, jnp.int32), B)
        merged_last = last_token.at[rows_eff].set(first, mode="drop")
        return merged_t, merged_p, merged_last

    def _admit_rows_fn(self, R: int, Tp: int, max_seq: int) -> Callable:
        fn = self._sliced_cache.get((R, Tp, max_seq))
        if fn is None:
            def admit_rows_fn(params, t_cache, p_state, last_token, prompts,
                              lengths, rows, valid, key):
                self.admit_trace_log.append((Tp, R))  # lint: allow[T106] intentional trace-time counter; tier-1 tests assert on it
                fresh = self._fresh_prefill(params, prompts, lengths,
                                            max_seq)
                return self._scatter_admitted(
                    (t_cache, p_state, last_token), fresh, rows, valid, key,
                    Tp)

            fn = jax.jit(admit_rows_fn)
            self._sliced_cache[(R, Tp, max_seq)] = fn
        return fn

    def admit_rows(self, state: SessionState, prompts: jnp.ndarray, lengths,
                   rows, *, valid=None, key: Optional[jax.Array] = None
                   ) -> SessionState:
        """Row-SLICED admission: prefill only the admitted rows.

        The compact counterpart of :meth:`admit`: ``prompts`` holds just
        the R admitted requests (R <= pool), the fresh prefill runs at the
        (R, T_prompt) shape — its cost scales with what was admitted — and
        the resulting target cache rows / proposer state rows / first
        sampled tokens are row-scattered into the live session
        (models/model.scatter_cache_rows + ``Proposer.scatter_state``).
        Works on dense and paged sessions alike (the fresh prefill is
        always dense; a paged session receives it through its block
        table, which the caller's ``PageAllocator`` must already map).

        Parameters
        ----------
        state : SessionState
            The live session.
        prompts : jnp.ndarray
            (R, T_prompt) admitted prompts, row-count-bucketed by the
            caller (pad lanes replicate real rows and are dropped via
            ``valid``).
        lengths : array-like
            (R,) true prompt lengths.
        rows : array-like
            (R,) pool row index each admitted request lands in.  DATA —
            which rows admit never retraces; only a new (R, T_prompt)
            shape does (logged in ``admit_trace_log`` as ``(T_prompt, R)``).
        valid : array-like, optional
            (R,) bool; False lanes are padding and scatter nothing.
        key : jax.Array, optional
            PRNG key for the admitted rows' first sampled tokens.

        Returns
        -------
        SessionState
            The live session with the admitted rows prefilled and ready
            for the next ``round``.
        """
        R, Tp = prompts.shape
        if key is None:
            if self.temperature > 0.0:
                raise ValueError(
                    "admit_rows() needs a fresh per-call key at "
                    "temperature>0 (split one per admission)")
            key = jax.random.PRNGKey(0)
        valid = (np.ones((R,), bool) if valid is None
                 else np.asarray(valid, bool))
        fn = self._admit_rows_fn(R, Tp, state.max_seq)
        t_cache, p_state, last_token = fn(
            state.params, state.t_cache, state.p_state, state.last_token,
            self._host(prompts, np.int32), self._host(lengths, np.int32),
            self._host(rows, np.int32), self._host(valid, bool), key)
        return replace(state, t_cache=t_cache, p_state=p_state,
                       last_token=last_token)

    # ------------------------------------------------ prefix-shared admission
    def _admit_prefix_fn(self, R: int, Tt: int, Tp: int,
                         max_seq: int) -> Callable:
        fn = self._prefix_cache.get((R, Tt, Tp, max_seq))
        if fn is None:
            target, proposer = self.target, self.proposer

            def prefix_fn(params, t_cache, p_state, last_token, tails,
                          tail_start, tail_len, prompts, lengths, rows,
                          valid, key):
                self.prefix_trace_log.append((Tt, R))  # lint: allow[T106] intentional trace-time counter; tier-1 tests assert on it
                rows_i = jnp.asarray(rows, jnp.int32)
                # compact R-row view of the LIVE paged cache: pool leaves
                # are batch-free (shared physical pages), so only the
                # block table and lengths need row-slicing.  The tail
                # extend writes through the sliced table into the rows'
                # private pages and attends across their shared-prefix
                # pages in the same forward.
                compact = {
                    "layers": t_cache["layers"],
                    "lengths": tail_start,
                    "pages": {"table": t_cache["pages"]["table"][rows_i]},
                }
                if proposer.needs_hidden:
                    logits, hidden, pend = target.extend_with_hidden(
                        params["target"], tails, compact, collect=False)
                else:
                    logits, pend = target.extend(params["target"], tails,
                                                 compact, collect=False)
                    hidden = None
                idx = (tail_len - 1)[:, None, None].astype(jnp.int32)
                last_l = jnp.take_along_axis(logits, idx, axis=1)[:, 0]
                last_h = (jnp.take_along_axis(hidden, idx, axis=1)[:, 0]
                          if hidden is not None else None)
                fresh_p = proposer.init_state(params, prompts, max_seq,
                                              lengths=lengths,
                                              last_hidden=last_h)
                first = sample_from(
                    probs_from_logits(last_l, self.temperature), key,
                    self.temperature)
                B = last_token.shape[0]
                rows_eff = jnp.where(valid, rows_i, B)
                # attention slots commit in place (pend carries the
                # written pools); the live lengths jump straight to the
                # full prompt length — shared prefix included
                merged_t = self._constrain_cache(dict(
                    t_cache, layers=pend["layers"],
                    lengths=t_cache["lengths"].at[rows_eff].set(
                        lengths, mode="drop")))
                merged_p = proposer.scatter_state(p_state, fresh_p, rows_i,
                                                  valid=valid)
                merged_last = last_token.at[rows_eff].set(first, mode="drop")
                return merged_t, merged_p, merged_last

            fn = jax.jit(prefix_fn)
            self._prefix_cache[(R, Tt, Tp, max_seq)] = fn
        return fn

    def admit_rows_prefix(self, state: SessionState, tails, tail_start,
                          tail_len, prompts, lengths, rows, *, valid=None,
                          key: Optional[jax.Array] = None) -> SessionState:
        """Prefix-SHARED sliced admission: target-prefill only the tails.

        The page-sharing counterpart of :meth:`admit_rows` for a PAGED
        session whose allocator already mapped each admitted row's table
        to a sibling's shared prefix pages (``PageAllocator.fork_prefix``
        + ``cow_range`` + private ``extend_row`` pages).  The target side
        prefills ONLY the unshared tail ``tails[i] = prompt[i][tail_start
        [i]:]`` as an extend at offset ``tail_start`` — the queries attend
        across the shared prefix KV through the row-sliced block table, so
        the common prefix is never recomputed.  The proposer still builds
        its (dense, cheap) state over the full prompt.

        Restriction: every target layer must be full-attention or MLA
        (pool-backed slots; SWA rings and recurrent states carry per-row
        dense state a tail extend cannot reconstruct) — callers gate on
        this and fall back to :meth:`admit_rows`.

        Parameters
        ----------
        state : SessionState
            The live PAGED session.
        tails : array-like
            (R, T_tail) unshared prompt tails, zero-padded per lane.
        tail_start : array-like
            (R,) shared-prefix length per row (where the tail starts).
        tail_len : array-like
            (R,) true tail lengths (``tail_start + tail_len == lengths``).
        prompts : array-like
            (R, T_prompt) FULL prompts — consumed by the proposer's fresh
            state build.
        lengths : array-like
            (R,) full prompt lengths.
        rows : array-like
            (R,) pool row of each admitted request (DATA, never retraces).
        valid : array-like, optional
            (R,) bool; False lanes are padding and scatter nothing.
        key : jax.Array, optional
            PRNG key for the admitted rows' first sampled tokens.

        Returns
        -------
        SessionState
            The live session with the admitted rows prefilled (shared
            prefix + fresh tail) and ready for the next ``round``.
        """
        tails = np.asarray(tails)
        prompts = np.asarray(prompts)
        R, Tt = tails.shape
        Tp = prompts.shape[1]
        if state.t_cache.get("pages") is None:
            raise ValueError("admit_rows_prefix needs a paged session")
        bad = [k for k in self.target.cfg.layer_pattern
               if k not in ("attn", "mla")]
        if bad:
            raise ValueError(
                f"admit_rows_prefix requires pool-backed layers only; "
                f"target has {sorted(set(bad))} (fall back to admit_rows)")
        if key is None:
            if self.temperature > 0.0:
                raise ValueError(
                    "admit_rows_prefix() needs a fresh per-call key at "
                    "temperature>0 (split one per admission)")
            key = jax.random.PRNGKey(0)
        valid = (np.ones((R,), bool) if valid is None
                 else np.asarray(valid, bool))
        fn = self._admit_prefix_fn(R, Tt, Tp, state.max_seq)
        t_cache, p_state, last_token = fn(
            state.params, state.t_cache, state.p_state, state.last_token,
            self._host(tails, np.int32),
            self._host(tail_start, np.int32),
            self._host(tail_len, np.int32),
            self._host(prompts, np.int32),
            self._host(lengths, np.int32),
            self._host(rows, np.int32), self._host(valid, bool), key)
        return replace(state, t_cache=t_cache, p_state=p_state,
                       last_token=last_token)

    # ----------------------------------------------------- chunked admission
    def begin_admit_chunked(self, prompts, lengths, rows, *, chunk: int,
                            key: Optional[jax.Array] = None
                            ) -> "PendingAdmission":
        """Open a chunked (incremental) sliced admission.

        Long prompts prefill ``chunk`` tokens at a time — one
        ``admit_chunk`` call per decode-round boundary — so a single long
        admission no longer stalls the round it lands in.  The compact
        cache under construction attends only to its own already-written
        positions (the ``extend``-at-offset discipline), so chunked and
        one-shot prefills are token-identical.  The admitted rows stay
        OUT of the live session (inactive, shape-stable) until the final
        chunk scatters them in.

        Restriction: one chunked admission holds requests of EQUAL prompt
        length (callers admit long prompts one request at a time), and SWA
        targets need ``chunk <= SWA_RING_PAD + 1`` (ring eviction) — the
        serving engine validates both.
        """
        prompts = np.asarray(prompts)
        lengths = np.asarray(lengths, np.int32)
        if len(set(int(x) for x in lengths)) != 1:
            raise ValueError("chunked admission requires equal prompt "
                             "lengths; admit long prompts one at a time")
        if int(lengths[0]) <= chunk:
            raise ValueError("prompt fits one chunk; use admit_rows")
        if key is None:
            if self.temperature > 0.0:
                raise ValueError(
                    "begin_admit_chunked() needs a fresh key at "
                    "temperature>0")
            key = jax.random.PRNGKey(0)
        return PendingAdmission(prompts=prompts, lengths=lengths,
                                rows=np.asarray(rows, np.int32),
                                chunk=int(chunk), key=key)

    def _chunk_fn(self, stage: str, R: int, C: int, Tp: int,
                  max_seq: int) -> Callable:
        # "first"/"mid" never touch the full prompt, so they share one
        # compile across prompt buckets; only "final" keys on Tp
        cache_key = (stage, R, C, Tp if stage == "final" else 0, max_seq)
        fn = self._chunk_cache.get(cache_key)
        if fn is not None:
            return fn
        target, proposer = self.target, self.proposer

        if stage == "first":
            def chunk_fn(params, toks, lens):
                self.chunk_trace_log.append((stage, C, R))  # lint: allow[T106] intentional trace-time counter; tier-1 tests assert on it
                fresh_t = target.init_cache(R, max_seq)
                _, fresh_t = target.prefill(params["target"], toks, fresh_t,
                                            lengths=lens)
                return fresh_t
        elif stage == "mid":
            def chunk_fn(params, fresh_t, toks, n_row):
                self.chunk_trace_log.append((stage, C, R))  # lint: allow[T106] intentional trace-time counter; tier-1 tests assert on it
                _, pend = target.extend(params["target"], toks, fresh_t,
                                        collect=True)
                return target.commit(pend, n_row, collected=True)
        else:                                        # "final"
            def chunk_fn(params, t_cache, p_state, last_token, fresh_t,
                         toks, prompts, lengths, n_row, rows, valid, key):
                self.chunk_trace_log.append((stage, C, R))  # lint: allow[T106] intentional trace-time counter; tier-1 tests assert on it
                logits, hidden, pend = target.extend_with_hidden(
                    params["target"], toks, fresh_t, collect=True)
                fresh_t = target.commit(pend, n_row, collected=True)
                idx = (n_row - 1)[:, None, None].astype(jnp.int32)
                last_l = jnp.take_along_axis(logits, idx, axis=1)[:, 0]
                last_h = jnp.take_along_axis(hidden, idx, axis=1)[:, 0] \
                    if proposer.needs_hidden else None
                fresh_p = proposer.init_state(params, prompts, max_seq,
                                              lengths=lengths,
                                              last_hidden=last_h)
                return self._scatter_admitted(
                    (t_cache, p_state, last_token),
                    (fresh_t, fresh_p, last_l), rows, valid, key, Tp)

        fn = jax.jit(chunk_fn)
        self._chunk_cache[cache_key] = fn
        return fn

    def admit_chunk(self, state: SessionState, pa: "PendingAdmission"
                    ) -> Tuple[SessionState, Optional["PendingAdmission"]]:
        """Advance a chunked admission by ONE chunk.

        Non-final chunks touch only the pending compact cache (the live
        session is returned unchanged — its slots keep decoding); the
        final chunk commits the tail, builds the proposer state over the
        full prompt, samples the first tokens and scatters everything into
        the live session exactly like :meth:`admit_rows`.

        Returns ``(state, pending)`` — ``pending`` is ``None`` once the
        admission landed (the rows are then live).
        """
        R, Tp = pa.prompts.shape
        C = pa.chunk
        done = pa.consumed
        total = int(pa.lengths[0])
        take = min(C, total - done)
        toks = np.full((R, C), 0, np.int32)
        toks[:, :take] = pa.prompts[:, done:done + take]
        toks = self._host(toks, np.int32)
        n_row = self._host(np.full((R,), take, np.int32), np.int32)
        final = done + take >= total
        params = state.params
        if done == 0:
            fn = self._chunk_fn("first", R, C, Tp, state.max_seq)
            fresh_t = fn(params, toks,
                         self._host(np.minimum(pa.lengths, C), np.int32))
            return state, replace(pa, t_cache=fresh_t, consumed=take)
        if not final:
            fn = self._chunk_fn("mid", R, C, Tp, state.max_seq)
            fresh_t = fn(params, pa.t_cache, toks, n_row)
            return state, replace(pa, t_cache=fresh_t,
                                  consumed=done + take)
        fn = self._chunk_fn("final", R, C, Tp, state.max_seq)
        valid = self._host(np.ones((R,), bool), bool)
        t_cache, p_state, last_token = fn(
            params, state.t_cache, state.p_state, state.last_token,
            pa.t_cache, toks, self._host(pa.prompts, np.int32),
            self._host(pa.lengths, np.int32), n_row,
            self._host(pa.rows, np.int32), valid, pa.key)
        new_state = replace(state, t_cache=t_cache, p_state=p_state,
                            last_token=last_token)
        return new_state, None

    # ---------------------------------------------------------------- growth
    def grow_session(self, state: SessionState, new_max_seq: int, *,
                     pool_pages: Optional[int] = None,
                     max_pages: Optional[int] = None) -> SessionState:
        """Grow a PAGED session's logical capacity to ``new_max_seq``.

        Pads the target's physical page pool / block table
        (models/model.grow_cache_pages) and the proposer's dense caches
        (``Proposer.grow_state``) so a late-arriving request longer than
        anything the stream was sized for admits instead of raising.  A
        growth changes compiled shapes, so the next round/admit retraces —
        pow2 geometry amortizes that; events land in ``growth_log``.
        """
        from repro.models.model import grow_cache_pages
        t_cache = state.t_cache
        if t_cache.get("pages") is None:
            raise ValueError("grow_session: dense sessions are statically "
                             "sized; use a paged session (kv_layout='paged')")
        if pool_pages is not None:
            t_cache = grow_cache_pages(t_cache, pool_pages, max_pages)
        p_state = self.proposer.grow_state(state.p_state, new_max_seq)
        self.growth_log.append((new_max_seq, pool_pages))
        return replace(state, t_cache=t_cache, p_state=p_state,
                       max_seq=new_max_seq)

    # -------------------------------------------------------------- generate
    def generate(
        self,
        params_t,
        params_p,
        prompts: jnp.ndarray,               # (B, T_prompt)
        max_new_tokens: int,
        *,
        gamma: Optional[int] = None,
        max_seq: Optional[int] = None,
        lengths=None,
        key: Optional[jax.Array] = None,
        prefill_kwargs: Optional[dict] = None,
        timed: bool = False,
    ) -> Tuple[np.ndarray, SDStats]:
        """Run SD rounds until every sequence has >= max_new_tokens.

        A thin wave-mode wrapper over the session API: one ``start`` then
        ``round`` in a loop with every row active — continuous callers
        drive the same two methods with masks and mid-stream ``admit``.
        """
        B, Tp = prompts.shape
        gamma = self.gamma if gamma is None else gamma
        key = key if key is not None else jax.random.PRNGKey(0)
        if max_seq is None:
            max_seq = Tp + max_new_tokens + gamma + 2
        key, k_pre = jax.random.split(key)
        state = self.start(params_t, params_p, prompts, max_seq=max_seq,
                           lengths=lengths, key=k_pre,
                           prefill_kwargs=prefill_kwargs)

        out = np.zeros((B, max_new_tokens + gamma + 1), np.int32)
        n_out = np.zeros((B,), np.int32)
        # the first sampled token (from prefill) counts as generated
        out[:, 0] = np.asarray(state.last_token)
        n_out += 1

        stats = SDStats()
        while int(n_out.min()) < max_new_tokens:
            key, k_round = jax.random.split(key)
            state, res = self.round(state, gamma=gamma, key=k_round,
                                    timed=timed)
            if res.finite is not None and not bool(np.all(res.finite)):
                # Wave mode has no quarantine path: a permanently
                # non-finite row commits nothing every round and the
                # min()-driven loop would never terminate.  Fail loudly;
                # the continuous scheduler is the layer that degrades
                # gracefully (finish_reason="numerical_fault").
                bad = np.where(~np.asarray(res.finite))[0].tolist()
                raise RuntimeError(
                    f"non-finite verify logits in wave-mode rows {bad}; "
                    "use the continuous scheduler for quarantine")
            for b in range(B):
                n = int(res.n_commit[b])
                w = min(n, out.shape[1] - n_out[b])
                out[b, n_out[b]: n_out[b] + w] = res.committed[b, :w]
                n_out[b] += w
            stats.absorb_round(res, B)
        self.accumulate_prefetch_totals(stats)
        return out[:, :max_new_tokens], stats


# ---------------------------------------------------------------------------
# backwards-compatible entry points (pre-Proposer API)
# ---------------------------------------------------------------------------

class SpecDecoder(SDEngine):
    """Legacy shim: target + draft *model* pair == SDEngine("model").

    Prefer ``SDEngine(target, make_proposer("model", target, draft))``.
    """

    def __init__(self, target: Model, draft: Model, gamma: int = 4,
                 temperature: float = 0.0):
        super().__init__(
            target,
            make_proposer("model", target, draft, temperature=temperature),
            gamma=gamma, temperature=temperature)
        self.draft = draft


def _ar_session(model: Model, temperature: float) -> SDEngine:
    """AR generation reuses one persistent "none" session per
    (model, temperature) so repeated generate_ar calls don't re-jit the
    decode round.  Sessions hang off the model instance itself (not a
    global registry): they share its lifetime, so dropping the model
    releases the compiled rounds too."""
    per_model = getattr(model, "_ar_sessions", None)
    if per_model is None:
        per_model = model._ar_sessions = {}
    eng = per_model.get(temperature)
    if eng is None:
        eng = SDEngine(model,
                       make_proposer("none", model, temperature=temperature),
                       gamma=0, temperature=temperature)
        per_model[temperature] = eng
    return eng


def generate_ar(model: Model, params, prompts: jnp.ndarray,
                max_new_tokens: int, *, temperature: float = 0.0,
                lengths=None, key=None,
                prefill_kwargs: Optional[dict] = None) -> np.ndarray:
    """Plain autoregressive baseline (T_AR in the paper's speedup
    definition) — the gamma=0 / "none"-proposer path of SDEngine."""
    out, _ = _ar_session(model, temperature).generate(
        params, None, prompts, max_new_tokens, lengths=lengths, key=key,
        prefill_kwargs=prefill_kwargs)
    return out
