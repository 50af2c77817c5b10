"""Continuous-batching slot scheduler: round-level SD with in-flight admission.

The paper's central claim is that SD speedup for a sparse MoE is a function
of the LIVE batch size N(t).  Wave scheduling can only measure that —
finished sequences ride along as padding until the slowest request
completes, and {use_sd, gamma} is planned once per wave.  This module
*operates* it:

  * a fixed pool of ``max_batch`` KV-cache slots is decoded round-by-round
    through the session API (core/spec_decode.SDEngine.start/round/
    admit_rows),
  * a slot RETIRES the moment its request finishes (per-slot
    ``max_new_tokens``, optional ``eos_id`` early exit) — its row goes
    inactive via the round's ``active`` mask, which is data, so occupancy
    changes never retrace,
  * freed slots are REFILLED between rounds: queued requests (visible from
    their ``arrival_round`` on, so Poisson traces replay exactly) prefill
    into the retired rows via ``SDEngine.admit_rows`` — a ROW-SLICED
    prefill whose cost scales with the admitted rows at their own
    per-admission prompt bucket, not the pool at a stream-global bucket,
  * long prompts optionally prefill in fixed-size CHUNKS
    (``prefill_chunk``), one chunk per round boundary, so a single long
    admission no longer stalls the round it lands in,
  * with ``kv_layout="paged"`` the target cache is block-table paged
    (models/model.py): per-row page lists from a growable pool, so
    ``max_seq`` is only an initial logical capacity — a late-submitted
    long request GROWS the session instead of raising.  Dense streams
    instead REJECT the oversize request (``finish_reason="rejected"``)
    and keep serving,
  * with ``prefix_sharing=True`` (paged only) an admission whose prompt
    shares a page-aligned prefix with a LIVE slot's prompt forks that
    slot's prefix pages (refcounted, copy-on-write at the tail boundary
    — ``PageAllocator.fork_prefix``/``cow_range``) and target-prefills
    only the unshared tail via ``SDEngine.admit_rows_prefix``; same-round
    siblings with a common prefix are staggered one round so the first
    becomes the fork leader (docs/paged_attention.md), and
    ``admission_order="pressure"`` refills smallest-footprint-first when
    the free-page fraction drops below half,
  * every round consults ``AutoTuner.plan()`` on the LIVE slot count: as
    occupancy decays out of the speedup window the stream hands off SD→AR
    mid-flight (a gamma=0 round in the SAME session — no session switch,
    no state rebuild, and the draft cache stays reconcilable for SD
    re-entry when admissions push N(t) back up).

Per-round ``StepReport``s aggregate into the engine's existing
``WaveReport`` / ``session_stats()`` surfaces; the occupancy trajectory
they carry feeds the decay-aware predicted-vs-measured comparison in
core/analytics.py, and their ``admit_rows``/``admit_tokens`` fields feed
the admission-work accounting (``core/analytics.admission_work``,
``benchmarks/admission_sweep.py``).

This mirrors in-flight batching in TensorRT-LLM / continuous batching in
vLLM at round granularity: admission is batched at round boundaries (not
token boundaries) because one SD round commits a variable 1..gamma+1
tokens per slot.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from dataclasses import dataclass, field, replace as dc_replace
from typing import TYPE_CHECKING, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.spec_decode import PendingAdmission, SDStats, SessionState
from repro.data.tokenizer import PAD
from repro.models.model import PageAllocator, copy_cache_pages
from repro.serving.engine import WaveReport, _pow2_at_least

if TYPE_CHECKING:                                    # avoid runtime cycle
    from repro.serving.engine import Request, ServingEngine


def submit_poisson(engine: "ServingEngine", prompts, lengths, *,
                   rate: float, max_new_choices=(8, 16, 32),
                   seed: int = 0) -> List[int]:
    """Submit a Poisson-arrival, mixed-length workload to an engine.

    The continuous scheduler's unit of time is the decode ROUND: request i
    arrives at ``cumsum(Exp(1/rate))`` rounds (``rate`` = mean arrivals per
    round; ``rate <= 0`` submits everything at round 0) with a
    ``max_new_tokens`` drawn uniformly from ``max_new_choices`` — the
    mixed-completion-length traffic where wave scheduling pays the most
    padding.  Wave engines ignore ``arrival_round`` (they admit FIFO), so
    the same submission order drives both schedulers comparably.

    Returns the submitted uids in arrival order.
    """
    rate = float(rate)
    if not np.isfinite(rate) or rate < 0:
        raise ValueError(
            f"arrival rate must be a finite value >= 0, got {rate!r} "
            "(rate=0 submits the whole workload at round 0; a positive "
            "rate is mean arrivals per decode round)")
    if len(lengths) == 0:
        raise ValueError("submit_poisson: empty workload (no lengths)")
    if len(prompts) < len(lengths):
        raise ValueError(
            f"submit_poisson: {len(prompts)} prompts for {len(lengths)} "
            "lengths — every length needs a prompt row")
    if not max_new_choices:
        raise ValueError("submit_poisson: max_new_choices must be "
                         "non-empty")
    for i in range(len(lengths)):
        if int(lengths[i]) < 1:
            raise ValueError(
                f"submit_poisson: prompt {i} is empty (length "
                f"{int(lengths[i])}); prefill needs >= 1 token — drop it "
                "from the workload instead")
    rng = np.random.default_rng(seed)
    t, uids = 0.0, []
    for i in range(len(lengths)):
        if rate > 0:
            t += rng.exponential(1.0 / rate)
        uids.append(engine.submit(
            np.asarray(prompts[i][: int(lengths[i])]),
            max_new_tokens=int(rng.choice(max_new_choices)),
            arrival_round=int(t)))
    return uids


@dataclass
class SlotState:
    """One KV-cache row of the continuous pool.

    ``active`` rows advance in SD rounds; inactive rows are shape-stable
    padding awaiting admission.  ``tokens`` accumulates the request's
    generated ids (the admission prefill's sampled token first), ``n_out``
    counts them against the request's ``max_new_tokens``.  ``admit_seq``
    is the stream-global admission sequence number — preemption picks its
    victim by it (youngest admitted, oldest protected).
    """
    index: int
    request: Optional["Request"] = None
    active: bool = False
    n_out: int = 0
    tokens: List[int] = field(default_factory=list)
    admit_seq: int = -1


@dataclass
class StepReport:
    """One SD round of a continuous stream.

    ``live`` is the active-slot count the round decoded (the N(t) the
    tuner planned on), ``committed`` the tokens credited to requests this
    round (budget/eos truncation applied), ``admitted``/``retired`` the
    slot churn at this round's boundary.  ``admit_rows``/``admit_tokens``
    are the rows and row-tokens the boundary's admission prefills actually
    processed (chunked-prefill chunk steps included) — the work the sliced
    path keeps ∝ what was admitted.

    Resilience fields (docs/faults.md; all zero on a healthy round):
    ``preempted`` slots evicted for page pressure at this boundary,
    ``faults`` rows quarantined by the numerical sentinel, ``timeouts``
    requests retired over their round budget, ``deferred`` admissions
    pushed back by watermark backpressure, transient admission failure,
    or a prefix-sharing stagger.

    ``shared_tokens`` counts prompt tokens this boundary's admissions did
    NOT prefill because prefix sharing mapped them to a sibling's pages
    (docs/paged_attention.md) — the per-round admission work saved.

    Host time (``time.perf_counter`` seconds; docs/metrics.md, "Tracing a
    live engine"): ``round_time`` is ``RoundResult.round_time``, dispatch
    to the round's one fetch; ``dispatch_time`` is
    ``RoundResult.dispatch_time``, the round call's entry until its
    programs are dispatched; ``admit_time`` is this boundary's admission
    dispatches plus the first-token fetch that waits on their prefill
    (the ``serve.admit`` and ``serve.admit_fetch`` spans), 0 when nothing
    was admitted.  ``experts_reached`` is ``RoundResult.experts_reached``:
    the distinct experts the verify reached per target MoE layer, summed
    over the layers (None when the target does not count them).
    """
    round_index: int
    live: int
    gamma: int
    used_sd: bool
    committed: int
    admitted: int
    retired: int
    round_time: float
    admit_rows: int = 0
    admit_tokens: int = 0
    preempted: int = 0
    faults: int = 0
    timeouts: int = 0
    deferred: int = 0
    shared_tokens: int = 0
    dispatch_time: float = 0.0
    admit_time: float = 0.0
    experts_reached: Optional[int] = None


@dataclass
class _Chunking:
    """A slot reserved by an in-flight chunked admission."""
    slot: SlotState
    request: "Request"
    pa: PendingAdmission


class ContinuousScheduler:
    """Round-level slot scheduler over one persistent decoding session.

    Owns the slot pool, the round loop and the admission policy (sliced /
    full, chunked prefill, paged growth); the engine supplies sessions,
    tuner, PRNG splits, layout knobs and the request queue.  One
    ``run_stream()`` call drains the queue (idling through rounds where
    every admissible request is still in flight or yet to arrive) and
    returns an aggregated ``WaveReport`` with per-round ``StepReport``s in
    ``.steps``.
    """

    def __init__(self, engine: "ServingEngine", *,
                 slots: Optional[int] = None):
        self.engine = engine
        self.pool = slots if slots is not None else engine.max_batch
        self._alloc: Optional[PageAllocator] = None
        self._admit_seq = 0                  # stream-global admission order
        self._hiwater: dict = {}             # uid -> max tokens ever committed
        self._consec_faulty = 0
        self._consec_stall = 0
        self._forced_ar = False
        self._admit_s = 0.0                  # this boundary's admit_time

    # ------------------------------------------------------------- admission
    def _pop_admissible(self, round_idx: int) -> Optional["Request"]:
        """Pop the first queued request visible at this round.

        Scans past non-admissible entries instead of head-checking: retry
        backoff and preemption requeue push ``arrival_round`` into the
        future, and a deferred request at the head must not block
        admissible work behind it.

        With ``admission_order="pressure"`` and a TIGHT pool (free page
        fraction below half), the smallest-page-footprint admissible
        request is picked instead of the oldest: more refills land per
        round under pressure, fewer growths/preemptions fire.  FIFO order
        resumes the moment pressure clears, and the preemption policy's
        oldest-slot protection is unaffected."""
        q = self.engine.queue
        pressured = (self.engine.admission_order == "pressure"
                     and self._alloc is not None
                     and self._alloc.free_fraction() < 0.5)
        best = None                           # (pages, queue index)
        for i, r in enumerate(q):
            if r.arrival_round <= round_idx:
                if not pressured:
                    del q[i]
                    return r
                key = (self._alloc.pages_for(self._need(r)), i)
                if best is None or key < best:
                    best = key
        if best is None:
            return None
        r = q[best[1]]
        del q[best[1]]
        return r

    def _has_admissible(self, round_idx: int) -> bool:
        return any(r.arrival_round <= round_idx for r in self.engine.queue)

    def _need(self, r: "Request") -> int:
        """Cache positions request ``r`` can touch over its lifetime.

        Re-admission after preemption needs no extra margin: the resumed
        tokens it recompute-prefills count against the same
        ``max_new_tokens`` budget they were first committed under."""
        return len(r.prompt) + r.max_new_tokens + self._g_max + 2

    def _admit_toks(self, r: "Request") -> np.ndarray:
        """The tokens a (re-)admission prefills: the prompt, plus — after
        a preemption — the already-committed tokens, so the recompute
        prefill reconstructs the row's KV exactly where it left off."""
        if r.resume_tokens:
            return np.concatenate([np.asarray(r.prompt, np.int32),
                                   np.asarray(r.resume_tokens, np.int32)])
        return np.asarray(r.prompt, np.int32)

    def _count(self, name: str, n: int = 1) -> None:
        c = self.engine.fault_counters
        c[name] = c.get(name, 0) + n

    # -------------------------------------------------------- prefix sharing
    @staticmethod
    def _common_prefix(a, b) -> int:
        n = min(len(a), len(b))
        if n == 0:
            return 0
        neq = np.nonzero(np.asarray(a[:n]) != np.asarray(b[:n]))[0]
        return int(neq[0]) if neq.size else n

    def _find_leader(self, slots: List[SlotState], r: "Request"
                     ) -> Tuple[Optional[SlotState], int]:
        """The ACTIVE slot whose prompt shares the longest common prefix
        with ``r``'s, or (None, 0).

        The share length is capped at ``len(r.prompt) - 1`` — the tail
        must keep at least one token for the admission extend to produce
        a next-token logit — and floored at ``page_size``: a sub-page
        overlap shares zero whole pages, so the fork would save nothing
        and the request admits normally."""
        best, best_len = None, 0
        for s in slots:
            if not s.active or s.request is None:
                continue
            share = min(self._common_prefix(s.request.prompt, r.prompt),
                        len(r.prompt) - 1)
            if share > best_len:
                best, best_len = s, share
        if best_len < self.engine.page_size:
            return None, 0
        return best, best_len

    def _should_stagger(self, r: "Request", batch_in, prefix_in, landed,
                        chunking) -> bool:
        """True when no ACTIVE leader exists but a sibling admitted at
        THIS round boundary shares >= one page of prompt prefix with
        ``r`` — pushing ``r`` one round lets it fork the sibling's pages
        once they are live instead of prefilling the prefix twice.  Each
        uid staggers at most once, so a sibling that never activates
        (instant eos, rejection) cannot orbit the queue."""
        if r.uid in self._staggered:
            return False
        ps = self.engine.page_size
        siblings = [q for _, q in batch_in] \
            + [q for _, q, _ in prefix_in] \
            + [q for _, q in landed] \
            + [c.request for c in chunking]
        for q in siblings:
            if min(self._common_prefix(q.prompt, r.prompt),
                   len(r.prompt) - 1) >= ps:
                return True
        return False

    def _bucket(self, n: int) -> int:
        return _pow2_at_least(n) if self.engine.bucket_batches else n

    def _swa_capacity_floor(self) -> int:
        """Minimum paged logical capacity so every SWA ring allocates at
        its FULL width (window + pad) from round 0.  Rings are dense and
        bounded — sizing them below full width only saves memory when the
        stream never grows, and a growth cannot resize a live ring
        (``pos % w`` would remap entries), so a paged session must never
        start below this."""
        from repro.models.attention import SWA_RING_PAD
        floor = 0
        for m in (self.engine.target, self.engine.draft):
            cfg = getattr(m, "cfg", None)
            if cfg is not None and any(
                    k == "swa" for k in getattr(cfg, "layer_pattern", ())):
                floor = max(floor, cfg.sliding_window + SWA_RING_PAD)
        return floor

    def _open_session(self, sess, max_seq: int) -> SessionState:
        """Open the pool with 1-token fillers; every REAL request then
        enters through the (sliced/chunked) admission path, so admission
        cost is accounted uniformly and the prompt bucket is always
        per-admission."""
        eng = self.engine
        B = self.pool
        toks = np.full((B, 1), PAD, np.int32)
        cache_opts, table = None, None
        if self._alloc is not None:
            cache_opts = {"paged": True, "page_size": eng.page_size,
                          "pool_pages": self._alloc.pool_pages}
            table = self._alloc.table
        params_d = None if eng.proposer_kind == "none" else eng.params_d
        # host arrays go in raw: the session's _host boundary places them
        # (replicated under a mesh) so admission keeps one jit signature
        return sess.start(eng.params_t, params_d, toks,
                          max_seq=max_seq,
                          lengths=np.ones((B,), np.int32),
                          key=eng._next_key(), cache_opts=cache_opts,
                          page_table=table)

    def _sync_table(self, state: SessionState) -> SessionState:
        """Push the allocator's (host) block table into the session —
        an input-array swap, never a retrace.  Under a mesh the swap is
        device_put with the SAME cache_spec placement the session opened
        with, so sharded rounds never see a placement flip."""
        eng = self.engine
        with jax.profiler.TraceAnnotation("serve.table_sync"):
            table = np.asarray(self._alloc.table, np.int32)
            if eng.mesh is not None:
                from jax.sharding import NamedSharding
                from repro.distributed.sharding import cache_spec
                new = jax.device_put(table, NamedSharding(
                    eng.mesh, cache_spec("pages/table", table.shape,
                                         mesh=eng.mesh)))
            else:
                new = jnp.asarray(table)
        pages = dict(state.t_cache["pages"], table=new)
        return dc_replace(state, t_cache=dict(state.t_cache, pages=pages))

    def _grow(self, sess, state: SessionState, pool_pages: int,
              max_pages: int, chunking: List["_Chunking"]) -> SessionState:
        """Adopt a grown paged geometry: pad the session's page pool /
        logical capacity, mirror it in the allocator, and pad in-flight
        chunked admissions' compact caches so their final scatter still
        matches the grown session."""
        from repro.models.model import grow_cache_seq
        alloc = self._alloc
        new_cap = max_pages * alloc.page_size
        with jax.profiler.TraceAnnotation("serve.grow"):
            state = sess.grow_session(state, new_cap, pool_pages=pool_pages,
                                      max_pages=max_pages)
            alloc.grow(pool_pages, max_pages)
            state = self._sync_table(state)
            for c in chunking:
                if c.pa.t_cache is not None:
                    c.pa = dc_replace(c.pa, t_cache=grow_cache_seq(
                        c.pa.t_cache, self.engine.target.cfg, new_cap))
        return state

    def _headroom_ok(self, need_pages: int, live: int) -> bool:
        """Watermark backpressure check: would admitting ``need_pages``
        leave the pool's free fraction above the configured watermark?
        Always true on an idle pool — deferring the only admissible work
        for headroom's sake would deadlock the stream."""
        wm = self.engine.resilience.free_page_watermark
        if wm <= 0 or live == 0:
            return True
        alloc = self._alloc
        left = len(alloc.free) - need_pages
        return left / max(alloc.pool_pages - 1, 1) >= wm

    def _preempt_victim(self, slots: List[SlotState],
                        incoming: "Request") -> Optional[SlotState]:
        """The youngest non-protected active slot, or None.

        Protected: the OLDEST admitted slot (head-of-line work always
        completes, so page pressure cannot livelock the stream), and the
        whole pool when the incoming request has itself been preempted —
        an already-requeued request waits for organic frees instead of
        starting an eviction cycle."""
        if incoming.preempt_count > 0:
            return None
        cands = [s for s in slots if s.active and s.request is not None]
        if len(cands) < 2:
            return None
        cands.sort(key=lambda s: s.admit_seq)
        return cands[-1]

    def _preempt(self, slot: SlotState, round_idx: int) -> None:
        """Evict one active slot under page pressure (vLLM-style
        recompute preemption): its pages return to the pool, the request
        requeues with its committed tokens saved in ``resume_tokens`` so
        re-admission recompute-prefills ``prompt + committed`` — no
        progress is lost, only recomputed."""
        r = slot.request
        r.resume_tokens = list(slot.tokens)
        r.preempt_count += 1
        r.requeue_round = round_idx
        r.arrival_round = round_idx + 1      # not re-admissible this round
        self._hiwater[r.uid] = max(self._hiwater.get(r.uid, 0),
                                   len(slot.tokens))
        slot.request = None
        slot.active = False
        slot.tokens = []
        self._alloc.free_row(slot.index)     # table row -> trash page 0
        self._table_dirty = True
        self.engine.queue.append(r)
        self._count("preemptions")
        self._round_preempted += 1

    def _make_room(self, sess, state: SessionState, r: "Request",
                   chunking: List["_Chunking"], round_idx: int, live: int,
                   slots: List[SlotState],
                   fresh_pages: Optional[int] = None
                   ) -> Tuple[SessionState, str]:
        """Make the paged pool able to admit ``r``; returns a verdict.

        ``"ok"``         — pages are available (caller allocs).
        ``"defer"``      — transient pressure (watermark, or exhaustion
                           with no preemptible victim); requeue and retry.
        ``"impossible"`` — the request cannot fit even a fully-drained
                           pool at ``max_pool_pages``; reject it.

        ``fresh_pages`` (prefix-sharing admissions) is how many pages the
        admission actually withdraws from the free list — the private
        tail plus the copy-on-write boundary page — which is less than
        the request's full footprint because the shared prefix pages are
        a sibling's.  Logical capacity (``max_seq``, table width) is
        still checked against the FULL footprint: the row's table must
        address every position it can ever touch.

        Resolution order under pressure: GROW (pow2, the cheap path) while
        ``max_pool_pages`` allows, then PREEMPT the youngest non-protected
        slot, then defer.  The loop terminates: every iteration either
        grows the pool (bounded by the cap) or frees a victim's pages
        (bounded by the active slot count).
        """
        alloc = self._alloc
        cap = self.engine.resilience.max_pool_pages
        need = self._need(r)
        need_pages = alloc.pages_for(need)
        fresh = need_pages if fresh_pages is None else fresh_pages
        if cap is not None and need_pages > cap - 1:
            return state, "impossible"
        while True:
            if (need > state.max_seq or need_pages > alloc.max_pages
                    or fresh > len(alloc.free)):
                pool_pages, max_pages = alloc.grown_geometry(need)
                if cap is not None and pool_pages > cap:
                    victim = self._preempt_victim(slots, r)
                    if victim is None:
                        return state, "defer"
                    self._preempt(victim, round_idx)
                    continue
                state = self._grow(sess, state, pool_pages, max_pages,
                                   chunking)
                continue
            if not self._headroom_ok(fresh, live):
                pool_pages = alloc.pool_pages * 2
                if cap is not None and pool_pages > cap:
                    return state, "defer"    # watermark backpressure
                state = self._grow(sess, state, pool_pages,
                                   alloc.max_pages, chunking)
                continue
            return state, "ok"

    def _finish_request(self, r: "Request", reason: str) -> None:
        """Finish a request that holds no slot (rejected / admit_failed /
        aborted from the queue).  A preempted request aborted before
        re-admission keeps its recoverable prefix as partial output."""
        if r.finish_reason is not None:
            raise RuntimeError(
                f"request {r.uid} already finished "
                f"{r.finish_reason!r}; refusing to overwrite with "
                f"{reason!r} — every request finishes exactly once")
        r.output = np.asarray(list(r.resume_tokens or []), np.int32)
        r.finish_reason = reason
        r.finished_at = time.perf_counter()
        self.engine.done[r.uid] = r
        self._finished.append(r)

    def _reject(self, r: "Request") -> None:
        """Refuse one request without killing the stream (dense layout:
        the cache was sized at stream start and cannot hold it; paged:
        it cannot fit even a drained pool at ``max_pool_pages``)."""
        self._finish_request(r, "rejected")

    @contextmanager
    def _admission(self, span: str):
        """A profiler span around admission work (``serve.admit`` /
        ``serve.admit_fetch``) whose host seconds add to this boundary's
        ``StepReport.admit_time``."""
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(span):
                yield
        finally:
            self._admit_s += time.perf_counter() - t0

    def _admit_batch(self, sess, state: SessionState,
                     batch_in: List[Tuple[SlotState, "Request"]]
                     ) -> Tuple[SessionState, int, int]:
        """One admission prefill for this round's refills.

        Sliced (default): only the admitted rows, at a prompt bucket
        computed FRESH from this batch (no stream-lifetime ratchet), row-
        count bucketed pow2 with padding lanes replicated round-robin and
        dropped from the scatter.  Full (legacy, kept for the admission
        benchmark's old-vs-sliced comparison): the whole pool is prefilled
        and non-admitted rows discarded via the admit mask.

        Returns ``(state, prefill_rows, prefill_tokens)`` — the work the
        call actually dispatched.
        """
        eng = self.engine
        seqs = [self._admit_toks(r) for _, r in batch_in]
        t_new = max(len(t) for t in seqs)
        Tp = self._bucket(t_new)
        key = eng._next_key()                 # one fresh key per admission
        if eng.admit_mode == "full":
            B = self.pool
            toks = np.full((B, Tp), PAD, np.int32)
            lengths = np.ones((B,), np.int32)
            mask = np.zeros((B,), bool)
            for (s, _), t in zip(batch_in, seqs):
                toks[s.index, : len(t)] = t
                lengths[s.index] = len(t)
                mask[s.index] = True
            state = sess.admit(state, toks, lengths, mask, key=key)
            return state, B, B * Tp
        R = min(self._bucket(len(batch_in)), self.pool)
        toks = np.full((R, Tp), PAD, np.int32)
        lengths = np.ones((R,), np.int32)
        rows = np.zeros((R,), np.int32)
        valid = np.zeros((R,), bool)
        for i in range(R):
            s, _ = batch_in[i % len(batch_in)]     # pad lanes replicate
            t = seqs[i % len(batch_in)]
            toks[i, : len(t)] = t
            lengths[i] = len(t)
            rows[i] = s.index
            valid[i] = i < len(batch_in)
        state = sess.admit_rows(state, toks, lengths, rows, valid=valid,
                                key=key)
        return state, R, R * Tp

    def _admit_batch_prefix(self, sess, state: SessionState,
                            batch_in: List[Tuple[SlotState, "Request", int]]
                            ) -> Tuple[SessionState, int, int]:
        """One TAIL-ONLY admission prefill for this round's prefix-shared
        refills (``SDEngine.admit_rows_prefix``).

        The allocator already forked each admitted row's table onto its
        leader's prefix pages and detached the CoW boundary, so the target
        prefills only the unshared tail ``prompt[share_len:]`` as an
        extend at offset ``share_len`` — the tail queries attend across
        the shared prefix KV through the block table.  The proposer still
        prefills the full prompt (its dense cache is private per row).
        Pad lanes replicate real rows round-robin: their duplicate tail
        writes land identical values on the same pages and the admit mask
        drops their state merges, exactly like ``_admit_batch``.

        Returns ``(state, prefill_rows, prefill_tokens)`` counting the
        TARGET-side tail work — the saving prefix sharing exists for.
        """
        eng = self.engine
        tails = [np.asarray(r.prompt[sl:], np.int32)
                 for _, r, sl in batch_in]
        proms = [np.asarray(r.prompt, np.int32) for _, r, _ in batch_in]
        Tt = self._bucket(max(len(t) for t in tails))
        Tp = self._bucket(max(len(p) for p in proms))
        R = min(self._bucket(len(batch_in)), self.pool)
        tail_toks = np.full((R, Tt), PAD, np.int32)
        prom_toks = np.full((R, Tp), PAD, np.int32)
        tail_start = np.zeros((R,), np.int32)
        tail_len = np.ones((R,), np.int32)
        lengths = np.ones((R,), np.int32)
        rows = np.zeros((R,), np.int32)
        valid = np.zeros((R,), bool)
        for i in range(R):
            s, r, sl = batch_in[i % len(batch_in)]
            t = tails[i % len(batch_in)]
            p = proms[i % len(batch_in)]
            tail_toks[i, : len(t)] = t
            prom_toks[i, : len(p)] = p
            tail_start[i] = sl
            tail_len[i] = len(t)
            lengths[i] = len(p)
            rows[i] = s.index
            valid[i] = i < len(batch_in)
        state = sess.admit_rows_prefix(state, tail_toks, tail_start,
                                       tail_len, prom_toks, lengths, rows,
                                       valid=valid, key=eng._next_key())
        return state, R, R * Tt

    # ------------------------------------------------------------ completion
    def _append(self, slot: SlotState, tokens: List[int]) -> int:
        """Credit round tokens to a slot; retire it on budget/eos.

        Returns the number of tokens actually credited (commits past the
        request's budget or its eos are discarded — SD can overshoot
        within a round)."""
        r = slot.request
        eos = self.engine.eos_id
        credited = 0
        for t in tokens:
            if slot.n_out >= r.max_new_tokens:
                break
            slot.tokens.append(int(t))
            slot.n_out += 1
            credited += 1
            if eos is not None and int(t) == eos:
                self._finish(slot, "eos")
                return credited
        if slot.n_out >= r.max_new_tokens:
            self._finish(slot, "length")
        return credited

    def _finish(self, slot: SlotState, reason: str) -> None:
        r = slot.request
        if r.finish_reason is not None:
            raise RuntimeError(
                f"request {r.uid} already finished {r.finish_reason!r}; "
                f"refusing to overwrite with {reason!r} — every request "
                "finishes exactly once")
        if len(slot.tokens) < self._hiwater.get(r.uid, 0):
            raise RuntimeError(
                f"request {r.uid} finishing with {len(slot.tokens)} "
                f"tokens < high-water {self._hiwater[r.uid]} — committed "
                "tokens went BACKWARD across a requeue")
        r.output = np.asarray(slot.tokens, np.int32)
        r.finish_reason = reason
        r.finished_at = time.perf_counter()
        self.engine.done[r.uid] = r
        self._finished.append(r)
        slot.request = None
        slot.active = False
        slot.tokens = []
        self._retired_rows.append(slot.index)

    # ------------------------------------------------------------------ loop
    def run_stream(self) -> Optional[WaveReport]:
        """Serve the queued stream to completion; one aggregated report.

        The loop per round: (1) advance every in-flight chunked admission
        by one chunk (landed ones activate their slot); (2) retire/refill
        — admit every admissible request into free slots with one sliced
        prefill, rejecting (dense) or growing for (paged) requests the
        stream wasn't sized for; (3) re-plan — ``tuner.plan(live)`` on the
        live slot count, SD→AR handoff via gamma=0 when the plan says so;
        (4) decode one SD round with the active mask; (5) credit tokens
        per slot, applying per-slot budgets and eos, freeing pages of
        retired rows.  Returns ``None`` on an empty queue.

        Profiler spans (docs/metrics.md, "Tracing a live engine"):
        ``serve.stream`` holds the whole call; each round has a
        ``serve.boundary`` (steps 1-3, up to the round call) and a
        ``serve.credit`` (step 5), and the boundary holds ``serve.admit``,
        ``serve.admit_fetch``, ``serve.table_sync`` and ``serve.grow``.
        """
        with jax.profiler.TraceAnnotation("serve.stream"):
            return self._run_stream()

    def _run_stream(self) -> Optional[WaveReport]:
        eng = self.engine
        if not eng.queue:
            return None
        kind = eng.proposer_kind
        sess = eng._session(kind)
        pending = list(eng.queue)
        # the cache must hold every plannable gamma's verify overshoot
        g_cands = [eng.gamma]
        if eng.tuner is not None:
            g_cands += [int(g) for g in getattr(eng.tuner, "gammas", ())]
        self._g_max = g_max = max(g_cands)

        paged = eng.kv_layout == "paged"
        if paged:
            ps = eng.page_size
            # logical capacity sized on what is VISIBLE at round 0 only —
            # later arrivals grow the session instead of inflating it now
            visible = [r for r in pending if r.arrival_round <= 0] \
                or pending[:1]
            cap = max(self._bucket(max(self._need(r) for r in visible)),
                      self._swa_capacity_floor())
            max_seq = -(-cap // ps) * ps
            pool_pages = 1 + sum(-(-self._need(r) // ps)
                                 for r in visible[: self.pool])
            self._alloc = PageAllocator(self.pool, ps,
                                        _pow2_at_least(pool_pages),
                                        max_seq // ps)
        else:
            # static sizing for the whole stream: the cache must hold the
            # longest KNOWN request; a later over-long submit is rejected
            # (finish_reason="rejected"), never fatal
            self._alloc = None
            max_seq = self._bucket(max(len(r.prompt) for r in pending)) \
                + max(r.max_new_tokens for r in pending) + g_max + 2
            if eng.bucket_batches:
                max_seq = _pow2_at_least(max_seq)

        slots = [SlotState(i) for i in range(self.pool)]
        state = self._open_session(sess, max_seq)
        stats = SDStats()
        steps: List[StepReport] = []
        self._finished: List["Request"] = []
        self._retired_rows: List[int] = []
        chunking: List[_Chunking] = []
        rescfg = eng.resilience
        inj = eng.fault_injector
        self._consec_faulty = 0              # ladder state is per-stream
        self._consec_stall = 0
        self._forced_ar = False
        self._staggered = set()              # uids prefix-staggered once
        # prefix sharing forks PAGED prefix pages; the engine ctor already
        # validated layout and layer kinds, so the stream-level gate is
        # just the flag
        prefix_ok = paged and eng.prefix_sharing
        used_sd_any = False
        aborted = False
        first_gamma: Optional[int] = None
        round_idx = 0
        t_start = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("serve.boundary"):
                admit_credited, landed, n_retired = 0, [], 0
                admit_rows_n, admit_tokens, deferred_n = 0, 0, 0
                faults_n, timeouts_n, shared_tok_n = 0, 0, 0
                cow_pairs: List[Tuple[int, int]] = []
                prefix_in: List[Tuple[SlotState, "Request", int]] = []
                self._round_preempted = 0
                self._table_dirty = False
                self._admit_s = 0.0
                had_admissible = self._has_admissible(round_idx)
                if inj is not None and self._alloc is not None:
                    # scripted page holds: release expired, apply due ones
                    inj.page_service(round_idx, self._alloc)
                # ---- advance chunked admissions: one chunk per round boundary
                for c in list(chunking):
                    R, C = c.pa.prompts.shape[0], c.pa.chunk
                    with self._admission("serve.admit"):
                        state, pa = sess.admit_chunk(state, c.pa)
                    admit_rows_n += R
                    admit_tokens += R * min(C, c.pa.remaining)
                    if pa is None:
                        chunking.remove(c)
                        landed.append((c.slot, c.request))
                    else:
                        c.pa = pa
                # ---- admit: one sliced prefill covers every refill this round
                # (slots whose chunked admission just landed activate below —
                # reserve them so the refill loop can't double-admit the row;
                # a preemption inside _make_room frees its victim's slot, so
                # the free set is recomputed every iteration)
                claimed = {c.slot.index for c in chunking} \
                    | {s.index for s, _ in landed}
                batch_in: List[Tuple[SlotState, "Request"]] = []
                live_now = sum(1 for s in slots if s.active)
                if inj is not None and inj.admission_fails(round_idx):
                    # scripted transient admission failure: bounded
                    # retry-with-backoff for everything admissible this round
                    deferred_n += self._defer_admissible(round_idx)
                else:
                    while True:
                        free = [s for s in slots
                                if not s.active and s.index not in claimed]
                        if not free:
                            break
                        r = self._pop_admissible(round_idx)
                        if r is None:
                            break
                        if not paged and self._need(r) > max_seq:
                            self._reject(r)
                            continue
                        # ---- prefix sharing: fork a live sibling's prompt
                        # pages instead of re-prefilling the common prefix.
                        # Re-admissions after preemption never share — their
                        # resume stream diverges from every prompt — and a
                        # tail longer than the prefill chunk takes the plain
                        # chunked path instead of one oversized tail extend.
                        leader, share_len = None, 0
                        if prefix_ok and not r.resume_tokens:
                            leader, share_len = self._find_leader(slots, r)
                            if (leader is not None and eng.prefill_chunk
                                    and len(r.prompt) - share_len
                                    > eng.prefill_chunk):
                                leader, share_len = None, 0
                            if leader is None and self._should_stagger(
                                    r, batch_in, prefix_in, landed, chunking):
                                r.arrival_round = round_idx + 1
                                eng.queue.append(r)
                                self._staggered.add(r.uid)
                                deferred_n += 1
                                self._count("prefix_staggered")
                                continue
                        if paged:
                            while True:
                                fresh = None
                                if leader is not None:
                                    # private tail pages + the CoW boundary
                                    # page; the fork itself draws nothing
                                    fresh = (self._alloc.pages_for(
                                        self._need(r))
                                        - share_len // self._alloc.page_size)
                                state, verdict = self._make_room(
                                    sess, state, r, chunking, round_idx,
                                    live_now, slots, fresh_pages=fresh)
                                if leader is not None and not leader.active:
                                    # _make_room preempted the leader; its
                                    # pages are gone — re-budget unshared
                                    leader, share_len = None, 0
                                    continue
                                break
                            if verdict == "impossible":
                                self._reject(r)
                                continue
                            if verdict == "defer":
                                # backpressure applies to the whole boundary
                                r.arrival_round = round_idx + 1
                                eng.queue.append(r)
                                deferred_n += 1
                                self._count("admit_deferred")
                                break
                            free = [s for s in slots
                                    if not s.active and s.index not in claimed]
                            row = free[0].index
                            if leader is not None:
                                self._alloc.fork_prefix(leader.index, row,
                                                        share_len)
                                self._alloc.extend_row(row, self._need(r))
                                pairs = self._alloc.cow_range(
                                    row, share_len, self._need(r))
                                cow_pairs.extend(pairs)
                                shared_tok_n += share_len
                                self._count("prefix_hits")
                                self._count("prefix_shared_tokens", share_len)
                                self._count("cow_copies", len(pairs))
                            else:
                                self._alloc.alloc(row, self._need(r))
                            self._table_dirty = True
                        s = free[0]
                        claimed.add(s.index)
                        if r.admitted_at is None:      # kept across requeues
                            r.admitted_at = time.perf_counter()
                        s.admit_seq = self._admit_seq
                        self._admit_seq += 1
                        if leader is not None:
                            prefix_in.append((s, r, share_len))
                            continue
                        toks = self._admit_toks(r)
                        if eng.prefill_chunk and len(toks) > eng.prefill_chunk:
                            chunking.append(_Chunking(
                                s, r, sess.begin_admit_chunked(
                                    toks[None, :],
                                    np.array([len(toks)], np.int32),
                                    np.array([s.index], np.int32),
                                    chunk=eng.prefill_chunk,
                                    key=eng._next_key())))
                            continue
                        batch_in.append((s, r))
                if self._table_dirty:
                    # one table upload covers every page assignment AND every
                    # preemption this round: a freed victim's row must point
                    # at trash page 0 before the next decode, or its frozen
                    # lane would write into pages the pool has re-issued
                    state = self._sync_table(state)
                if cow_pairs:
                    # one bucketed device copy detaches every CoW boundary
                    # page this round; (0, 0) trash self-copies pad to pow2 so
                    # the copy dispatch stays shape-stable across rounds
                    n = _pow2_at_least(len(cow_pairs)) if eng.bucket_batches \
                        else len(cow_pairs)
                    padded = cow_pairs + [(0, 0)] * (n - len(cow_pairs))
                    state = dc_replace(state, t_cache=copy_cache_pages(
                        state.t_cache, padded))
                if prefix_in:
                    with self._admission("serve.admit"):
                        state, rows_n, toks_n = self._admit_batch_prefix(
                            sess, state, prefix_in)
                    admit_rows_n += rows_n
                    admit_tokens += toks_n
                    landed.extend((s, r) for s, r, _ in prefix_in)
                if batch_in:
                    with self._admission("serve.admit"):
                        state, rows_n, toks_n = self._admit_batch(
                            sess, state, batch_in)
                    admit_rows_n += rows_n
                    admit_tokens += toks_n
                    landed.extend(batch_in)
                if landed:
                    with self._admission("serve.admit_fetch"):
                        first = jax.device_get(state.last_token)
                    t_first = time.perf_counter()
                    for s, r in landed:
                        s.request, s.active = r, True
                        if r.first_token_at is None:   # kept across requeues
                            r.first_token_at = t_first
                        resume = list(r.resume_tokens or [])
                        # a re-admission resumes the committed stream: the
                        # recompute prefill already holds these tokens' KV,
                        # and crediting them AGAIN would double-count across
                        # the requeue — preload, don't re-append
                        s.n_out, s.tokens = len(resume), resume
                        if resume:
                            r.readmit_round = round_idx
                            r.resume_tokens = None
                            self._count("requeues")
                        # the admission prefill's sample is the first token
                        admit_credited += self._append(
                            s, [int(first[s.index])])
                n_retired = sum(1 for s, r in landed if not s.active)

                active_mask = np.array([s.active for s in slots], bool)
                live = int(active_mask.sum())
                if live == 0:
                    if landed or admit_rows_n:
                        # every admitted slot finished on its prefill token
                        # (1-token budgets / instant eos) or only chunk work
                        # ran: record the churn so steps never undercount
                        steps.append(StepReport(
                            round_idx, 0, 0, False, admit_credited,
                            len(landed), n_retired, 0.0, admit_rows_n,
                            admit_tokens, preempted=self._round_preempted,
                            deferred=deferred_n, shared_tokens=shared_tok_n,
                            admit_time=self._admit_s))
                    self._free_retired()
                    if not eng.queue and not chunking:
                        break
                    if self._note_stall(had_admissible,
                                        landed or admit_rows_n or n_retired):
                        aborted = True
                        self._abort(slots, chunking)
                        break
                    round_idx += 1                  # idle: awaiting arrivals
                    continue

                # ---- re-plan on the LIVE slot count (the paper's N(t))
                gamma, use_sd = eng.gamma, True
                if eng.tuner is not None:
                    plan = eng.tuner.plan(live)
                    gamma, use_sd = plan["gamma"], plan["use_sd"]
                if eng.force_sd is not None:
                    use_sd = eng.force_sd
                if kind == "none":
                    use_sd = False
                if self._forced_ar:
                    # degradation ladder rung 1: repeated faulty rounds force
                    # plain AR (gamma=0 in the SAME session) until a healthy
                    # round clears the cooldown — overrides even force_sd
                    use_sd = False
                if not use_sd:
                    gamma = 0                   # in-session SD→AR handoff
                if gamma > g_max:
                    # the cache margin was sized for g_max at stream start; a
                    # larger gamma would scatter verify KV past the allocated
                    # pages/rows, which JAX clamps SILENTLY — fail loudly
                    raise ValueError(
                        f"tuner planned gamma={gamma} > g_max={g_max} the "
                        "stream was sized for; expose the tuner's range via a "
                        "'gammas' attribute (AutoTuner does)")
                if first_gamma is None:
                    first_gamma = gamma
                used_sd_any |= use_sd

                # ---- scripted pre-round faults (testing only; inj is None in
                # production streams)
                t_r0 = time.perf_counter()
                if inj is not None:
                    from repro.serving.faults import poison_cache_row
                    for f in inj.nan_rows(round_idx):
                        row = f.row if f.row is not None else next(
                            (s.index for s in slots if s.active), None)
                        if row is not None:
                            state = dc_replace(state, t_cache=poison_cache_row(
                                state.t_cache, row))
                    delay = inj.slow_delay(round_idx)
                    if delay:
                        time.sleep(delay)
                round_key = eng._next_key()

            # ---- one SD round over the pool, retired rows masked out
            state, res = sess.round(state, gamma=gamma, key=round_key,
                                    active=active_mask,
                                    timed=eng.timed)
            round_wall = time.perf_counter() - t_r0

            with jax.profiler.TraceAnnotation("serve.credit"):
                # ---- numerical sentinel: quarantine non-finite rows before
                # crediting (their n_commit is already forced to 0 in-round,
                # so co-batched slots are untouched)
                if res.finite is not None and not bool(np.all(res.finite)):
                    for s in slots:
                        if s.active and not bool(res.finite[s.index]):
                            self._count("numerical_faults")
                            self._finish(s, "numerical_fault")
                            faults_n += 1
                            n_retired += 1
                credited = 0
                for s in slots:
                    if not s.active:
                        continue
                    n = int(res.n_commit[s.index])
                    credited += self._append(
                        s, list(res.committed[s.index, :n]))
                    if not s.active:
                        n_retired += 1
                # ---- per-request round budgets
                for s in slots:
                    if not s.active:
                        continue
                    s.request.rounds_used += 1
                    if (rescfg.max_rounds_per_request is not None
                            and s.request.rounds_used
                            >= rescfg.max_rounds_per_request):
                        self._count("timeouts")
                        self._finish(s, "timeout")
                        timeouts_n += 1
                        n_retired += 1
                self._free_retired()

                # live-weighted accounting: retired rows' masked lanes commit
                # nothing, so sigma/alpha describe the work actually requested
                stats.absorb_round(res, live)
                alpha_round = (float(res.n_accept.sum()) / (res.width * live)
                               if (use_sd and res.width and live) else None)
                if alpha_round is not None and eng.tuner is not None:
                    eng.tuner.update_alpha(alpha_round)
                steps.append(StepReport(round_idx, live, gamma, use_sd,
                                        admit_credited + credited,
                                        len(landed), n_retired,
                                        res.round_time, admit_rows_n,
                                        admit_tokens,
                                        preempted=self._round_preempted,
                                        faults=faults_n, timeouts=timeouts_n,
                                        deferred=deferred_n,
                                        shared_tokens=shared_tok_n,
                                        dispatch_time=res.dispatch_time,
                                        admit_time=self._admit_s,
                                        experts_reached=res.experts_reached))

                # ---- degradation ladder: consecutive faulty rounds escalate
                # healthy → forced AR → stream-level safe stop
                slow = (rescfg.round_deadline_s is not None
                        and round_wall > rescfg.round_deadline_s)
                if slow:
                    self._count("slow_rounds")
                collapsed = (rescfg.collapse_alpha > 0
                             and alpha_round is not None
                             and alpha_round < rescfg.collapse_alpha)
                if faults_n or slow or collapsed:
                    self._consec_faulty += 1
                    if (not self._forced_ar and self._consec_faulty
                            >= rescfg.faulty_rounds_to_ar):
                        self._forced_ar = True
                        self._count("ar_handoffs")
                    if self._consec_faulty >= rescfg.faulty_rounds_to_stop:
                        aborted = True
                        self._abort(slots, chunking)
                        break
                else:
                    self._consec_faulty = 0
                    self._forced_ar = False
                if self._note_stall(had_admissible,
                                    admit_credited + credited or landed
                                    or n_retired or admit_rows_n):
                    aborted = True
                    self._abort(slots, chunking)
                    break
            round_idx += 1

        if inj is not None and self._alloc is not None:
            inj.release_all(self._alloc)
        self._check_invariants()
        sess.accumulate_prefetch_totals(stats)
        wall = time.perf_counter() - t_start
        clean = ("length", "eos")
        n_tokens = sum(len(r.output) for r in self._finished
                       if r.finish_reason in clean)
        discarded = sum(len(r.output) for r in self._finished
                        if r.finish_reason not in clean)
        reasons: dict = {}
        for r in self._finished:
            reasons[r.finish_reason] = reasons.get(r.finish_reason, 0) + 1
        if aborted:
            self._count("aborts")
        return WaveReport(
            batch=len(self._finished),
            gamma=first_gamma if first_gamma is not None else 0,
            used_sd=used_sd_any, stats=stats, wall_time=wall,
            tokens_out=n_tokens, proposer=kind, bucket=self.pool,
            moe_dispatch=eng.moe_dispatch, scheduler="continuous",
            steps=steps, tokens_discarded=discarded,
            finish_reasons=reasons)

    # ------------------------------------------------------------ resilience
    def _defer_admissible(self, round_idx: int) -> int:
        """Bounded retry-with-backoff for a transiently failing admission
        round: attempt ``i`` pushes a request ``backoff * 2**(i-1)``
        rounds out; past ``admit_retries`` it finishes ``admit_failed``."""
        eng = self.engine
        rescfg = eng.resilience
        deferred = 0
        while True:
            r = self._pop_admissible(round_idx)
            if r is None:
                return deferred
            r.admit_attempts += 1
            if r.admit_attempts > rescfg.admit_retries:
                self._count("admit_failures")
                self._finish_request(r, "admit_failed")
                continue
            backoff = max(1, rescfg.admit_backoff_rounds
                          * 2 ** (r.admit_attempts - 1))
            r.arrival_round = round_idx + backoff
            eng.queue.append(r)
            self._count("admit_retries")
            deferred += 1

    def _note_stall(self, had_admissible: bool, progress) -> bool:
        """Stall watchdog: count consecutive rounds where admissible work
        existed but NOTHING landed, committed, or retired (an admission
        deadlock — e.g. page pressure with no growable/preemptible way
        out).  Returns True once the configured budget is exhausted."""
        if had_admissible and not progress:
            self._consec_stall += 1
        else:
            self._consec_stall = 0
        if self._consec_stall >= self.engine.resilience.stall_rounds:
            self._count("stalls")
            return True
        return False

    def _abort(self, slots: List[SlotState],
               chunking: List[_Chunking]) -> None:
        """Stream-level safe stop (ladder rung 2 / stall watchdog): every
        in-flight and queued request finishes ``aborted`` — partial
        output preserved — and every page returns to the pool, so the
        engine object stays serviceable for the next stream."""
        for c in list(chunking):
            if self._alloc is not None:
                self._alloc.free_row(c.slot.index)
            self._finish_request(c.request, "aborted")
        chunking.clear()
        for s in slots:
            if s.active:
                self._finish(s, "aborted")
        while self.engine.queue:
            self._finish_request(self.engine.queue.popleft(), "aborted")
        self._free_retired()

    def _check_invariants(self) -> None:
        """End-of-stream invariant check (cheap, always on): every request
        that entered the stream left with exactly ONE finish_reason (the
        overwrite guards in ``_finish``/``_finish_request`` enforce
        uniqueness; this checks presence), committed token counts are
        monotonic across requeues (``_finish`` checks against the
        high-water marks), and — paged — no page leaked: after the final
        ``_free_retired`` and the injector's ``release_all`` the
        allocator must be exactly as full as it started."""
        for r in self._finished:
            if r.finish_reason is None:
                raise RuntimeError(
                    f"request {r.uid} left the stream without a "
                    "finish_reason")
        if self._alloc is not None:
            self._alloc.assert_no_leaks()

    def _free_retired(self) -> None:
        """Return retired rows' pages to the pool (paged layout)."""
        if self._alloc is None:
            self._retired_rows.clear()
            return
        for row in self._retired_rows:
            self._alloc.free_row(row)
        self._retired_rows.clear()
