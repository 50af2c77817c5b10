"""Prefetch-aware proposer (SP-MoE, arXiv:2510.10302; offload-hiding SD,
arXiv:2508.21706) — warm the target's expert weights during the draft phase.

MoESD's serving analysis says the remaining verify-phase bottleneck for a
sparse MoE target is expert-weight movement: at moderate batch sizes only
N(t) < E experts activate, so the verify forward streams a routing-dependent
subset of the FFN weights from HBM.  The propose phase is dead time for the
target — SP-MoE's observation is that the draft token stream *names* the
tokens the next verify pass will process, so a cheap probe of the target's
routers over those tokens predicts which experts verify will hit, and their
weights can be warmed while drafting is still running.

``PrefetchProposer`` wraps any registered drafter (default: the paper's
small-model drafter) and adds the cross-phase coupling:

  1. PROPOSE   — delegate to the inner proposer (identical drafts, identical
                 PRNG stream → greedy outputs match the wrapped drafter
                 exactly).
  2. PROBE     — record the round's speculated stream [last_token, drafts],
                 embed it with the target's table, and push it through every
                 MoE layer's router (fp32, (P, d, E) per period-slot).  The
                 top-M experts by probe votes per slot become a
                 ``models/moe.PrefetchPlan``.
  3. WARM      — the engine (core/spec_decode.SDEngine) dispatches
                 ``models/moe.warm_experts`` on the plan *between* the
                 propose and verify launches; the gather of the predicted
                 experts' weights executes ahead of verify on the device
                 queue, overlapping the (host-side) verify dispatch instead
                 of serializing with it.
  4. SCORE     — verify runs through ``Model.verify`` with the plan, which
                 counts hits (activated AND warmed) vs misses per round;
                 the engine aggregates them into ``SDStats`` /
                 ``WaveReport`` / ``session_stats()``.

The probe reads only the embedding table and router matrices — a (N, d) x
(d, E) matmul per MoE slot, orders of magnitude below a draft forward — so
it rides inside the jitted propose stage without moving the propose/verify
cost balance the paper's speedup model depends on.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.proposer import make_proposer, register_proposer
from repro.models.moe import PrefetchPlan


def router_probe(params_t: dict, cfg, tokens: jnp.ndarray, *,
                 top_m: int) -> PrefetchPlan:
    """Predict the experts a verify pass over ``tokens`` will activate.

    Parameters
    ----------
    params_t : dict
        Target model params (embedding table + per-slot router matrices).
    cfg : ModelConfig
        Target config — supplies ``moe_pattern``, ``num_experts``,
        ``num_experts_per_tok``, ``num_periods``.
    tokens : jnp.ndarray
        (B, T) speculated verify stream ([last_token, drafts]).
    top_m : int
        Static number of experts to warm per (slot, period) — the plan's
        gather shape.

    Returns
    -------
    PrefetchPlan
        Per-slot (P, E) predicted-hot masks + (P, M) warm ids.  The probe
        applies each router to the raw token
        *embeddings* (the lightweight stand-in for that layer's true hidden
        states — the same approximation benchmarks/prefetch_utility.py
        validates against a trained router); top-k routing per token, then
        top-M experts per period by vote count, mean router probability as
        the tie-break.
    """
    E = max(cfg.num_experts, 1)
    P = cfg.num_periods
    K = max(cfg.num_experts_per_tok, 1)
    x = params_t["embed"]["table"][tokens.reshape(-1)]          # (N, d)
    masks, ids = [], []
    for i, is_moe in enumerate(cfg.moe_pattern):
        if not is_moe:
            masks.append(jnp.zeros((P, E), bool))
            ids.append(jnp.zeros((P, 0), jnp.int32))
            continue
        router = params_t["layers"][i]["ffn"]["router"]          # (P, d, E)
        logits = jnp.einsum("nd,pde->pne", x.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)                  # (P, N, E)
        _, topk = jax.lax.top_k(probs, K)                        # (P, N, K)
        # scatter-add vote count — never materialize a (P, N, K, E) one-hot
        # on the propose hot path (the same rule that keeps (N, K, E)
        # one-hots out of decode/verify, see models/transformer.py)
        pidx = jnp.broadcast_to(jnp.arange(P)[:, None, None], topk.shape)
        votes = jnp.zeros((P, E), jnp.float32).at[pidx, topk].add(1.0)
        score = votes + jnp.mean(probs, axis=1)                  # tie-break
        _, top_ids = jax.lax.top_k(score, top_m)                 # (P, M)
        mask = jnp.zeros((P, E), bool).at[
            jnp.arange(P)[:, None], top_ids].set(True)
        masks.append(mask)
        ids.append(top_ids.astype(jnp.int32))
    return PrefetchPlan(masks=tuple(masks), expert_ids=tuple(ids))


class PrefetchProposer:
    """Wrap a drafter with draft-phase expert warming (module docstring).

    Drafting is fully delegated — same tokens, same q distributions, same
    PRNG consumption — so greedy outputs are token-identical to the wrapped
    proposer's.  The wrapper only adds the router probe to ``propose`` (the
    resulting ``PrefetchPlan`` rides in the round work-state) and exposes
    ``provides_prefetch`` so the engine runs warm + scored-verify stages.

    Under expert-parallel sharded serving the plan is mesh-agnostic (global
    expert ids); LOCALITY lives in the warm gather itself —
    ``models/moe.warm_experts(..., mesh=...)`` runs as a shard_map in which
    each shard touches only the predicted experts of ITS local slice, so
    warming never streams another shard's weights across the interconnect.
    """

    kind = "prefetch"
    provides_prefetch = True

    def __init__(self, target, draft, temperature: float = 0.0, *,
                 inner: str = "model", top_m: Optional[int] = None):
        self.target = target
        self.inner = make_proposer(inner, target, draft,
                                   temperature=temperature)
        cfg = target.cfg
        E, K = max(cfg.num_experts, 1), max(cfg.num_experts_per_tok, 1)
        # warm budget: 2K experts per period-slot by default — roughly the
        # N(t) regime where prediction beats "warm everything" (t small).
        # User-supplied budgets are clamped to [1, E]: top_k inside the
        # jitted probe would otherwise fail opaquely for top_m > E
        self.top_m = min(E, max(1, int(top_m))) if top_m is not None \
            else min(E, 2 * K)

    @property
    def needs_hidden(self) -> bool:
        return self.inner.needs_hidden

    def init_state(self, params, prompts, max_seq, *, lengths=None,
                   last_hidden=None):
        return {"inner": self.inner.init_state(
            params, prompts, max_seq, lengths=lengths,
            last_hidden=last_hidden)}

    def propose(self, params, state, last_token, gamma, key):
        drafts, q_dist, work = self.inner.propose(
            params, state["inner"], last_token, gamma, key)
        # this round's draft stream IS the upcoming verify stream: probe it
        stream = jnp.concatenate([last_token[:, None], drafts], axis=1)
        plan = router_probe(params["target"], self.target.cfg, stream,
                            top_m=self.top_m)
        return drafts, q_dist, {"inner": work, "plan": plan}

    def commit(self, params, state, *, base_len, n_accept, n_commit,
               verify_tokens, hidden):
        return {"inner": self.inner.commit(
            params, state["inner"], base_len=base_len, n_accept=n_accept,
            n_commit=n_commit, verify_tokens=verify_tokens, hidden=hidden)}

    def merge_state(self, old, new, mask):
        """Admission merge: fully delegated (the plan is round work-state,
        never part of the persistent between-rounds state)."""
        return {"inner": self.inner.merge_state(old["inner"], new["inner"],
                                                mask)}

    def scatter_state(self, old, new, rows, *, valid=None):
        """Sliced admission: fully delegated to the wrapped drafter."""
        return {"inner": self.inner.scatter_state(old["inner"], new["inner"],
                                                  rows, valid=valid)}

    def grow_state(self, state, new_max_seq):
        """Session growth: fully delegated to the wrapped drafter."""
        return {"inner": self.inner.grow_state(state["inner"], new_max_seq)}


register_proposer("prefetch", PrefetchProposer)
