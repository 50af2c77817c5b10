"""Serving engine: request queue → batched speculative decoding → completions.

Private-serving shape (the paper's target scenario, Sec. 3.4): tens of
concurrent requests, batched together, decoded with SD.  Two schedulers:

  * ``scheduler="wave"`` — admit up to ``max_batch`` requests per
    generation wave (static batch per wave, continuous across waves), run
    SD rounds until EVERY sequence in the wave is done.  Finished rows ride
    along as padding until the slowest request completes, and the AutoTuner
    is consulted once per wave.
  * ``scheduler="continuous"`` — a fixed pool of KV-cache slots decoded
    round-by-round (serving/scheduler.py): slots retire the moment their
    request finishes (per-request ``max_new_tokens``, optional ``eos_id``),
    freed slots are refilled by a masked prefill BETWEEN rounds (zero
    retraces within a batch bucket), and the AutoTuner re-plans
    {use_sd, gamma} on the LIVE slot count every round — the paper's
    N(t)-dependence operated, not just measured.

Either way the engine:

  * consults the AutoTuner (core/autotune.py, beyond-paper) to pick
    {use_sd, gamma} from the fitted perf model,
  * holds ONE persistent decoding session (core/spec_decode.SDEngine) per
    proposer kind — "model" / "eagle" / "none" via the Proposer registry —
    so compiled SD rounds are reused across waves instead of re-jitting a
    fresh decoder every wave.  Batches are padded up to power-of-two
    buckets and cache lengths are bucketed too, so the jit cache is keyed
    on (proposer_kind, gamma, batch_bucket) and a tuner-driven gamma change
    only adds one cache entry (returning to a seen gamma is compile-free),
  * reports per-wave SDStats (sigma, alpha, rounds, phase timings) and
    target-efficiency measurements, feeding alpha back into the tuner.

Every wave gets its own PRNG key split from the engine's root key, so
sampling is never correlated across waves.

Per-request sampling: each ``Request`` carries ``SamplingParams``
(serving/sampling.py).  ``max_new_tokens`` is honored per request (and per
SLOT in continuous mode); ``temperature``/``top_k``/``top_p`` must match
the engine's global policy — batched rejection sampling shares one
temperature across the batch — and ``submit`` fails loudly on mismatch
rather than silently decoding with the wrong policy.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.autotune import AutoTuner
from repro.core.proposer import make_proposer
from repro.core.spec_decode import SDEngine, SDStats
from repro.data.tokenizer import PAD
from repro.distributed.collectives import ep_load_report
from repro.distributed.constraints import resolve_mesh
from repro.distributed.sharding import shard_params
from repro.models.model import Model
from repro.serving.sampling import SamplingParams


@dataclass
class Request:
    uid: int
    prompt: np.ndarray                   # (T,) token ids
    max_new_tokens: int = 64
    temperature: float = 0.0
    output: Optional[np.ndarray] = None
    # host clock (time.perf_counter) at the request's lifecycle points:
    # submitted, taken from the queue into a slot (continuous) or a wave,
    # its first token on the host (continuous: after the admission
    # prefill's fetch), finished.  A preempted and re-admitted request
    # keeps its first admitted_at / first_token_at.
    submitted_at: float = field(default_factory=time.perf_counter)
    admitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    sampling: Optional[SamplingParams] = None
    # "length" | "eos" | "rejected" | "numerical_fault" | "timeout" |
    # "admit_failed" | "aborted"  (docs/faults.md has the full table)
    finish_reason: Optional[str] = None
    arrival_round: int = 0               # continuous mode: visible from here
    # ---- resilience traceability (continuous mode; docs/faults.md) ----
    preempt_count: int = 0               # times page pressure evicted us
    requeue_round: Optional[int] = None  # round of the LAST preemption
    readmit_round: Optional[int] = None  # round of the last re-admission
    resume_tokens: Optional[List[int]] = None  # committed tokens to replay
    rounds_used: int = 0                 # decode rounds spent on this slot
    admit_attempts: int = 0              # transient admission failures seen


def finish_output(tokens: np.ndarray, eos_id: Optional[int]):
    """Truncate a generated stream at the first ``eos_id`` (inclusive).

    Returns ``(tokens, reason)`` with reason "eos" if an eos fired before
    the length budget, else "length" — the per-request accounting both
    schedulers share, so ``WaveReport.tokens_out`` counts only REAL
    generated tokens."""
    tokens = np.asarray(tokens)
    if eos_id is not None:
        hits = np.nonzero(tokens == eos_id)[0]
        if hits.size:
            return tokens[: int(hits[0]) + 1], "eos"
    return tokens, "length"


@dataclass
class WaveReport:
    batch: int
    gamma: int
    used_sd: bool
    stats: Optional[SDStats]
    wall_time: float
    tokens_out: int
    proposer: str = "model"
    bucket: int = 0                       # padded batch actually decoded
    moe_dispatch: str = "onehot"          # target's decode dispatch mode
    scheduler: str = "wave"               # "wave" | "continuous"
    steps: Optional[list] = None          # continuous: per-round StepReports
    # continuous-mode resilience accounting: committed tokens belonging to
    # requests that did NOT finish cleanly ("rejected"/"timeout"/
    # "numerical_fault"/"admit_failed"/"aborted") or that were discarded by
    # a preempt-and-requeue.  Excluded from ``tokens_out`` so tokens/sec
    # reflects only useful delivered work; never double-counts a requeued
    # request's recomputed prefix.
    tokens_discarded: int = 0
    finish_reasons: Optional[Dict[str, int]] = None  # reason -> count
    # expert-parallel telemetry (mesh-sharded "ep" dispatch only): the
    # finished outputs' per-shard expert-load counts, load imbalance
    # (max/mean) and modeled per-device a2a volume
    # (distributed.collectives.ep_load_report); None otherwise
    ep: Optional[dict] = None

    @property
    def tokens_per_second(self) -> float:
        return self.tokens_out / max(self.wall_time, 1e-9)

    # per-phase decode timings (propose/verify/reject populated when the
    # engine runs with timed=True; round_time is always real wall time)
    @property
    def round_time(self) -> float:
        return self.stats.round_time if self.stats else 0.0

    @property
    def propose_time(self) -> float:
        return self.stats.propose_time if self.stats else 0.0

    @property
    def verify_time(self) -> float:
        return self.stats.verify_time if self.stats else 0.0

    @property
    def reject_time(self) -> float:
        return self.stats.reject_time if self.stats else 0.0

    @property
    def warm_time(self) -> float:
        return self.stats.warm_time if self.stats else 0.0

    # expert-prefetch accounting (prefetch-aware waves; zero otherwise)
    @property
    def prefetch_hits(self) -> int:
        return self.stats.prefetch_hits if self.stats else 0

    @property
    def prefetch_misses(self) -> int:
        return self.stats.prefetch_misses if self.stats else 0

    @property
    def prefetch_hit_rate(self) -> float:
        return self.stats.prefetch_hit_rate if self.stats else 0.0


def _pow2_at_least(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


class ServingEngine:
    def __init__(
        self,
        target: Model,
        draft=None,                         # Model | EagleHead | None
        params_t=None,
        params_d=None,
        *,
        max_batch: int = 32,
        tuner: Optional[AutoTuner] = None,
        gamma: int = 4,
        temperature: float = 0.0,
        force_sd: Optional[bool] = None,
        proposer: str = "model",            # registered proposer kind
        proposer_opts: Optional[dict] = None,  # extra factory kwargs for it
        draft_kind: Optional[str] = None,   # deprecated alias for proposer
        seed: int = 0,
        timed: bool = False,
        bucket_batches: bool = True,
        scheduler: str = "wave",            # "wave" | "continuous"
        eos_id: Optional[int] = None,       # early-exit token (both modes)
        kv_layout: str = "dense",           # "dense" | "paged" (continuous)
        page_size: int = 64,                # paged: positions per KV page
        prefill_chunk: Optional[int] = None,  # continuous: chunked prefill
        admit_mode: str = "sliced",         # "sliced" | "full" (legacy)
        prefix_sharing: bool = False,       # paged: fork shared prompt prefixes
        admission_order: str = "fifo",      # "fifo" | "pressure" refill order
        resilience=None,                    # Optional[ResilienceConfig]
        fault_injector=None,                # Optional[FaultInjector] (tests)
        mesh=None,                          # Optional[Mesh]: sharded serving
        mesh_layout: Optional[str] = None,  # "tp" | "fsdp" (with mesh)
    ):
        if scheduler not in ("wave", "continuous"):
            raise ValueError(f"scheduler must be 'wave' or 'continuous', "
                             f"got {scheduler!r}")
        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"kv_layout must be 'dense' or 'paged', "
                             f"got {kv_layout!r}")
        if admit_mode not in ("sliced", "full"):
            raise ValueError(f"admit_mode must be 'sliced' or 'full', "
                             f"got {admit_mode!r}")
        if kv_layout == "paged":
            if scheduler != "continuous":
                raise ValueError("kv_layout='paged' is a continuous-serving "
                                 "layout; wave decoding sizes caches per "
                                 "wave already")
            if admit_mode == "full":
                raise ValueError("admit_mode='full' merges same-shape "
                                 "caches and cannot address a paged pool; "
                                 "use the sliced path with paged KV")
        if admission_order not in ("fifo", "pressure"):
            raise ValueError(f"admission_order must be 'fifo' or "
                             f"'pressure', got {admission_order!r}")
        if admission_order == "pressure" and kv_layout != "paged":
            raise ValueError("admission_order='pressure' orders refills by "
                             "page footprint; it requires kv_layout='paged'")
        if prefix_sharing:
            if kv_layout != "paged":
                raise ValueError(
                    "prefix_sharing maps common prompt prefixes to shared "
                    "KV pages; it requires kv_layout='paged' (continuous "
                    "scheduler)")
            bad = [k for k in target.cfg.layer_pattern
                   if k not in ("attn", "mla")]
            if bad:
                raise ValueError(
                    f"prefix_sharing forks block-table pages; target layer "
                    f"kinds {sorted(set(bad))} keep dense per-row state "
                    "(SWA rings / recurrent columns) that a table fork "
                    "cannot share — serve this model without sharing")
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError(f"prefill_chunk must be >= 1, got "
                                 f"{prefill_chunk}")
            if scheduler != "continuous":
                raise ValueError("prefill_chunk interleaves with decode "
                                 "rounds; it requires scheduler="
                                 "'continuous'")
            from repro.models.attention import SWA_RING_PAD
            if (any(k == "swa" for k in target.cfg.layer_pattern)
                    and prefill_chunk > SWA_RING_PAD + 1):
                raise ValueError(
                    f"prefill_chunk={prefill_chunk} > SWA_RING_PAD+1="
                    f"{SWA_RING_PAD + 1}: a larger chunk evicts ring "
                    "entries still inside earlier chunk queries' windows")
        # ------- expert-parallel sharded serving (docs/distributed.md) ----
        # the mesh is threaded EXPLICITLY: engine → model constraints / ep
        # dispatch → SDEngine sessions (host placement + cache_spec); no
        # process-global mesh state (constraints.set_mesh is removed)
        if mesh is not None:
            mesh, mesh_layout = resolve_mesh(mesh, mesh_layout)
            if "model" not in mesh.axis_names:
                raise ValueError(
                    f"ServingEngine(mesh=...) needs a 'model' axis for the "
                    f"expert/TP dimension; got axes {mesh.axis_names} "
                    "(launch/mesh.make_ep_mesh builds a ('data','model') "
                    "mesh)")
            if getattr(target, "mesh", None) is None:
                target.mesh = mesh
                target.mesh_layout = mesh_layout
            if isinstance(draft, Model) and draft.mesh is None:
                draft.mesh = mesh
                draft.mesh_layout = mesh_layout
            # expert weights shard over "model" (EP), attention/router per
            # param_spec; placed once here so every session reuses them
            if params_t is not None:
                params_t = jax.device_put(
                    params_t, shard_params(params_t, mesh,
                                           layout=mesh_layout))
            if params_d is not None:
                params_d = jax.device_put(
                    params_d, shard_params(params_d, mesh,
                                           layout=mesh_layout))
        self.mesh = mesh
        self.mesh_layout = mesh_layout
        self.proposer_kind = draft_kind if draft_kind is not None else proposer
        self.proposer_opts = dict(proposer_opts or {})
        self.target, self.draft = target, draft
        self.params_t, self.params_d = params_t, params_d
        self.max_batch = max_batch
        self.tuner = tuner
        self.gamma = gamma
        self.temperature = temperature
        self.force_sd = force_sd
        self.timed = timed
        self.bucket_batches = bucket_batches
        self.scheduler = scheduler
        self.eos_id = eos_id
        self.kv_layout = kv_layout
        self.page_size = page_size
        self.prefill_chunk = prefill_chunk
        self.admit_mode = admit_mode
        self.prefix_sharing = prefix_sharing
        self.admission_order = admission_order
        if resilience is None:
            from repro.serving.faults import ResilienceConfig
            resilience = ResilienceConfig()
        if (resilience.round_deadline_s is not None
                or resilience.max_rounds_per_request is not None
                or fault_injector is not None) and scheduler != "continuous":
            raise ValueError(
                "resilience deadlines and fault injection are continuous-"
                "scheduler features (wave mode has no per-round requeue "
                "path); use scheduler='continuous'")
        self.resilience = resilience
        self.fault_injector = fault_injector
        # fault/preemption/recovery counters, filled by the continuous
        # scheduler and surfaced via session_stats()["resilience"]
        self.fault_counters: Dict[str, int] = {}
        self.queue: Deque[Request] = deque()
        self.done: Dict[int, Request] = {}
        self.reports: List[WaveReport] = []
        self._uid = 0
        self._key = jax.random.PRNGKey(seed)
        # persistent decoding sessions, one per proposer kind — constructed
        # exactly once and reused for every wave (compile-cache lives inside)
        self._sessions: Dict[str, SDEngine] = {}
        self.session_constructions: Dict[str, int] = {}
        self._slot_scheduler = None         # lazy ContinuousScheduler

    # ----------------------------------------------------------------- queue
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 64, *,
               sampling: Optional[SamplingParams] = None,
               arrival_round: int = 0) -> int:
        """Queue one request.

        Parameters
        ----------
        prompt : array-like
            (T,) token ids.
        max_new_tokens : int
            Generation budget (ignored if ``sampling`` is given — its
            ``max_new_tokens`` wins).
        sampling : SamplingParams, optional
            Per-request sampling policy.  ``max_new_tokens`` is honored per
            request; ``temperature`` must equal the engine's and
            ``top_k``/``top_p`` must be off — batched rejection sampling
            shares one distribution policy across the batch, so a mismatch
            raises ``ValueError`` instead of silently decoding with the
            wrong policy (build one engine per policy).
        arrival_round : int
            Continuous mode: the request becomes admissible only from this
            decode round on (workload drivers use it to replay
            Poisson-arrival traces).  Wave mode ignores it.

        Returns
        -------
        int
            The request uid (key into ``self.done`` once finished).
        """
        sp = sampling if sampling is not None else SamplingParams(
            temperature=self.temperature, max_new_tokens=max_new_tokens)
        if sp.temperature != self.temperature:
            raise ValueError(
                f"per-request temperature {sp.temperature} != engine "
                f"temperature {self.temperature}: batched rejection sampling "
                "shares one temperature across the batch — submit matching "
                "requests or build an engine per policy")
        if sp.top_k > 0 or sp.top_p < 1.0:
            raise ValueError(
                "top_k/top_p are not supported on the speculative-decoding "
                "path (rejection sampling needs the full target/draft "
                "distributions); submit with default top_k=0, top_p=1.0")
        self._uid += 1
        self.queue.append(Request(self._uid, np.asarray(prompt, np.int32),
                                  sp.max_new_tokens, sp.temperature,
                                  sampling=sp, arrival_round=arrival_round))
        return self._uid

    def _admit(self) -> List[Request]:
        wave = []
        while self.queue and len(wave) < self.max_batch:
            wave.append(self.queue.popleft())
        t = time.perf_counter()
        for r in wave:
            r.admitted_at = t
        return wave

    @property
    def moe_dispatch(self) -> str:
        """The target model's MoE dispatch mode for this engine's decodes
        (launch/serve defaults it to "gmm" — the ragged serving kernels)."""
        return getattr(self.target, "moe_dispatch", "onehot")

    def _ep_telemetry(self, outputs) -> Optional[dict]:
        """Per-wave expert-parallel load report over the finished outputs
        (``WaveReport.ep``): per-shard expert loads, imbalance, and modeled
        a2a volume.  None unless this is a mesh-sharded "ep" engine."""
        if self.mesh is None or self.moe_dispatch != "ep":
            return None
        toks = [np.asarray(o).reshape(-1) for o in outputs if o is not None]
        toks = (np.concatenate(toks) if toks
                else np.zeros((0,), np.int32))
        return ep_load_report(self.params_t, self.target.cfg, toks,
                              int(self.mesh.shape["model"]))

    # -------------------------------------------------------------- sessions
    def _session(self, kind: str) -> SDEngine:
        """The long-lived decoding session for one proposer kind."""
        sess = self._sessions.get(kind)
        if sess is None:
            # kind-specific factory opts only apply to the configured kind
            # (never to the "none" AR-fallback session)
            opts = self.proposer_opts if kind == self.proposer_kind else {}
            prop = make_proposer(kind, self.target,
                                 None if kind == "none" else self.draft,
                                 temperature=self.temperature, **opts)
            sess = SDEngine(self.target, prop, gamma=self.gamma,
                            temperature=self.temperature, mesh=self.mesh,
                            mesh_layout=self.mesh_layout)
            self._sessions[kind] = sess
            self.session_constructions[kind] = \
                self.session_constructions.get(kind, 0) + 1
        return sess

    def session_stats(self) -> Dict[str, dict]:
        """Per-proposer-kind session health: reuse, traces, prefetch totals.

        Returns
        -------
        dict
            One entry per proposer kind this engine has served, each with:

            ``constructions`` : int
                Times the session was built (always 1 per kind — waves
                reuse sessions; tests assert on it).
            ``gammas_compiled`` : list of int
                Gammas with a built (fused or staged) decode round.
            ``traces`` : list of (gamma, batch)
                Every jit retrace the session performed; a wave that reuses
                a compiled round adds nothing here.
            ``admit_traces`` : list of (prompt_bucket, rows)
                Every continuous-admission retrace.  Sliced admissions
                key on the ADMITTED row bucket (rows << pool for typical
                refills); the legacy full path keys on the pool.  Which
                rows admit is data and adds nothing here.
            ``chunk_traces`` : list of (stage, chunk, rows)
                Chunked-prefill retraces ("first"/"mid"/"final" stage
                functions, compiled once per shape).
            ``prefix_traces`` : list of (tail_bucket, rows)
                Prefix-shared tail-admission retraces
                (``SDEngine.admit_rows_prefix``; empty unless the engine
                runs with ``prefix_sharing=True``).
            ``growths`` : list of (new_max_seq, pool_pages)
                Paged-session capacity growths (each one retrace, pow2-
                amortized).
            ``prefetch`` : dict
                Session-lifetime expert-warmup aggregates ``{"hits",
                "actual", "predicted", "rounds", "hit_rate"}`` summed over
                all waves (all zero unless the kind is prefetch-aware).

            Plus ONE reserved non-kind entry, ``"resilience"``: the
            continuous scheduler's fault/preemption/recovery counters
            (``preemptions``, ``requeues``, ``numerical_faults``,
            ``slow_rounds``, ``timeouts``, ``admit_deferred``, ... —
            docs/faults.md).  Empty dict for wave mode / healthy streams.
        """
        out = {"resilience": dict(self.fault_counters)}
        for kind, sess in self._sessions.items():
            totals = dict(sess.prefetch_totals)
            totals["hit_rate"] = totals["hits"] / max(totals["actual"], 1)
            out[kind] = {
                "constructions": self.session_constructions.get(kind, 0),
                "gammas_compiled": sess.compiled_gammas(),
                "traces": list(sess.trace_log),
                "admit_traces": list(sess.admit_trace_log),
                "chunk_traces": list(sess.chunk_trace_log),
                "prefix_traces": list(sess.prefix_trace_log),
                "growths": list(sess.growth_log),
                "prefetch": totals,
            }
        return out

    # ------------------------------------------------------------------ wave
    def _bucket(self, B: int) -> int:
        if not self.bucket_batches:
            return B
        return min(_pow2_at_least(B), self.max_batch)

    def _pad_prompts(self, wave: List[Request], rows: int):
        """Pad the wave to ``rows`` sequences (bucket) x pow2 prompt length.
        Pad rows replicate real requests round-robin (so wave stats weight
        each request near-equally rather than over-counting one sequence)
        and are discarded after decode."""
        T = max(len(r.prompt) for r in wave)
        if self.bucket_batches:
            T = _pow2_at_least(T)
        toks = np.full((rows, T), PAD, np.int32)
        lengths = np.zeros((rows,), np.int32)
        for i in range(rows):
            r = wave[i % len(wave)]
            toks[i, : len(r.prompt)] = r.prompt
            lengths[i] = len(r.prompt)
        return jnp.asarray(toks), jnp.asarray(lengths)

    def _next_key(self) -> jax.Array:
        self._key, k = jax.random.split(self._key)
        return k

    def step(self, key: Optional[jax.Array] = None) -> Optional[WaveReport]:
        """Admit and decode one generation wave.

        Pops up to ``max_batch`` queued requests, consults the tuner for
        {use_sd, gamma} at the padded bucket size, decodes the wave through
        the persistent session for the active proposer kind, and records
        finished requests in ``self.done``.

        Parameters
        ----------
        key : jax.Array, optional
            PRNG key for this wave's sampling.  Default: a fresh split from
            the engine's root key (so waves are never key-correlated).

        Returns
        -------
        WaveReport or None
            The wave's report — batch/gamma/proposer, SDStats (sigma,
            alpha, per-phase timings, prefetch hit/miss counts for
            prefetch-aware waves), wall time and tokens/sec — or ``None``
            if the queue was empty.  ``tokens_out`` counts only real
            generated tokens: per-request ``max_new_tokens`` and eos
            truncation (``finish_reason``) are applied per request.
        """
        wave = self._admit()
        if not wave:
            return None
        B = len(wave)
        bucket = self._bucket(B)
        gamma, use_sd = self.gamma, True
        if self.tuner is not None:
            # plan for the batch size that actually executes (the padded
            # bucket), so policy and the alpha fed back describe one regime
            plan = self.tuner.plan(bucket)
            gamma, use_sd = plan["gamma"], plan["use_sd"]
        if self.force_sd is not None:
            use_sd = self.force_sd
        if self.proposer_kind == "none":
            use_sd = False
        kind = self.proposer_kind if use_sd else "none"
        if not use_sd:
            gamma = 0
        sess = self._session(kind)
        max_new = max(r.max_new_tokens for r in wave)
        toks, lengths = self._pad_prompts(wave, bucket)
        # bucket the cache length too so waves of similar shape share a
        # compiled round instead of retracing on every new max_seq
        max_seq = toks.shape[1] + max_new + gamma + 2
        if self.bucket_batches:
            max_seq = _pow2_at_least(max_seq)
        key = key if key is not None else self._next_key()

        t0 = time.perf_counter()
        out, stats = sess.generate(
            self.params_t, None if kind == "none" else self.params_d,
            toks, max_new, gamma=gamma, max_seq=max_seq, lengths=lengths,
            key=key, timed=self.timed)
        if use_sd and self.tuner is not None and stats.draft_events:
            self.tuner.update_alpha(stats.alpha)
        wall = time.perf_counter() - t0

        n_tokens = 0
        for i, r in enumerate(wave):                 # pad rows fall off here
            r.output, r.finish_reason = finish_output(
                out[i, : r.max_new_tokens], self.eos_id)
            r.finished_at = time.perf_counter()
            n_tokens += len(r.output)
            self.done[r.uid] = r
        report = WaveReport(B, gamma, use_sd, stats, wall, n_tokens,
                            proposer=kind, bucket=bucket,
                            moe_dispatch=self.moe_dispatch,
                            ep=self._ep_telemetry([r.output for r in wave]))
        self.reports.append(report)
        return report

    # ------------------------------------------------------------ continuous
    def step_continuous(self) -> Optional[WaveReport]:
        """Drain the queue through the continuous slot scheduler.

        One call serves the WHOLE queued stream (arrivals included, via
        ``Request.arrival_round``) round-by-round on a fixed pool of
        ``max_batch`` KV slots, re-planning {use_sd, gamma} on the live
        slot count every round.  Returns one aggregated WaveReport
        (``scheduler="continuous"``) whose ``steps`` carry the per-round
        StepReports, or ``None`` if the queue was empty.
        """
        from repro.serving.scheduler import ContinuousScheduler
        if self._slot_scheduler is None:
            self._slot_scheduler = ContinuousScheduler(self)
        before = set(self.done)
        report = self._slot_scheduler.run_stream()
        if report is not None:
            report.ep = self._ep_telemetry(
                [r.output for uid, r in self.done.items()
                 if uid not in before])
            self.reports.append(report)
        return report

    def run(self, key: Optional[jax.Array] = None) -> List[WaveReport]:
        """Drain the queue under the configured scheduler.  ``key``
        (optional) reseeds the engine's root key; every wave / round then
        decodes under its own split — never the same key twice."""
        if key is not None:
            self._key = key
        reports = []
        if self.scheduler == "continuous":
            while self.queue:
                r = self.step_continuous()
                if r:
                    reports.append(r)
            return reports
        while self.queue:
            r = self.step()
            if r:
                reports.append(r)
        return reports
