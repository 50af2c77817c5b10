"""Entry-point registry: where traced-ness starts, and what is known static.

The tracer lint discovers most entry points syntactically (``jax.jit``
call/decorator sites, ``pl.pallas_call`` kernels, callbacks handed to
``jax.lax`` control flow).  The registry supplements that discovery with
*annotations* the source cannot express:

* ``KNOWN_ENTRY_POINTS`` — hot-path functions that must be analyzed even
  when no jit site in the scanned roots reaches them syntactically (e.g. a
  proposer implementation only ever invoked through the ``Proposer``
  protocol).  Each names its statically-passed params; everything else is
  seeded traced.
* ``ALWAYS_STATIC_PARAMS`` — parameter names that are Python-static by
  repo-wide convention whenever traced-ness is *inferred* (``self``,
  ``cfg`` …).  Call-site flow still wins where a call site is visible.
* ``STATIC_RESULT_ATTRS`` / ``STATIC_RESULT_CALLS`` — attribute reads and
  calls whose result is static even on a traced operand (``x.shape``,
  ``len(x)``), so ``int(x.shape[0])`` never false-positives as a coercion.

Extending the registry (docs/analysis.md): add a :class:`KnownEntry` with
the module-path suffix, the function qualname and its static params —
nothing else; the dataflow takes it from there.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import FrozenSet, Optional, Tuple


@dataclass(frozen=True)
class KnownEntry:
    """One registered analysis root.

    ``module`` is a repo-relative path *suffix* (so the registry is stable
    under repo relocation), ``qualname`` the function's dotted name inside
    the module, ``static`` the params NOT seeded as traced.
    """
    module: str
    qualname: str
    static: Tuple[str, ...] = ()


#: Hot-path roots beyond what jit-site discovery reaches syntactically:
#: the SDEngine round/admission bodies reach these through protocol
#: dispatch (``proposer.*``) or method indirection (``target.*``); listing
#: them keeps the lint exhaustive even if an intermediate call becomes
#: unresolvable.
KNOWN_ENTRY_POINTS: Tuple[KnownEntry, ...] = (
    # target model surface (models/model.py) — reached from every round
    KnownEntry("models/model.py", "Model.prefill",
               static=("self", "collect")),
    KnownEntry("models/model.py", "Model.prefill_with_hidden",
               static=("self", "collect")),
    KnownEntry("models/model.py", "Model.extend",
               static=("self", "collect")),
    KnownEntry("models/model.py", "Model.extend_with_hidden",
               static=("self", "collect")),
    KnownEntry("models/model.py", "Model.verify",
               static=("self", "collect")),
    KnownEntry("models/model.py", "Model.commit",
               static=("self", "collected")),
    KnownEntry("models/model.py", "merge_cache_rows"),
    KnownEntry("models/model.py", "scatter_cache_rows",
               static=("n_prompt",)),
    # proposer implementations (protocol-dispatched from SDEngine stages)
    KnownEntry("core/proposer.py", "ModelProposer.propose",
               static=("self", "gamma")),
    KnownEntry("core/proposer.py", "ModelProposer.commit",
               static=("self",)),
    KnownEntry("core/proposer.py", "NoneProposer.propose",
               static=("self", "gamma")),
    KnownEntry("core/eagle.py", "EagleProposer.propose",
               static=("self", "gamma")),
    KnownEntry("core/eagle.py", "EagleProposer.commit",
               static=("self",)),
    KnownEntry("core/prefetch.py", "PrefetchProposer.propose",
               static=("self", "gamma")),
    # moe / attention forwards (reached through layer dispatch)
    KnownEntry("models/moe.py", "moe_forward",
               static=("cfg", "dispatch", "return_metrics", "mesh",
                       "mesh_layout")),
    KnownEntry("models/moe.py", "warm_experts", static=("cfg", "mesh")),
    # expert-parallel shard_map dispatch (distributed/collectives.py):
    # moe_ep_forward is the mesh entry, _ragged_ep_shard the per-shard
    # body (everything bound via functools.partial there is static)
    KnownEntry("distributed/collectives.py", "moe_ep_forward",
               static=("cfg", "mesh", "layout", "capacity_factor",
                       "interpret")),
    KnownEntry("distributed/collectives.py", "_ragged_ep_shard",
               static=("cfg", "slots", "activation", "model_axis",
                       "m_shards", "interpret")),
    # shard_map body of the expert-prefetch warm gather (models/moe.py):
    # nested def, reached only through the shard_map site, so jit-site
    # discovery never sees it
    KnownEntry("models/moe.py", "warm_experts._local_gather"),
    KnownEntry("distributed/constraints.py", "constrain",
               static=("kind", "mesh", "layout")),
    KnownEntry("models/attention.py", "attention_forward",
               static=("cfg",)),
    # paged decode/verify attention kernel (reached from gqa_forward's
    # paged extend branch; scale/logit_cap fold into the kernel closure)
    KnownEntry("kernels/decode_attention/ops.py", "paged_decode_attention",
               static=("scale", "logit_cap", "interpret", "mesh")),
    # numerical sentinel (serving/faults.py) — runs inside the jitted
    # verify stage on the raw logits every round
    KnownEntry("serving/faults.py", "logits_finite"),
    # batched rejection sampling (the REJECT stage) — temperature is a
    # Python float by contract (the greedy branch is a trace-time choice)
    KnownEntry("core/rejection.py", "rejection_sample",
               static=("temperature",)),
    KnownEntry("core/rejection.py", "sample_from",
               static=("temperature",)),
    KnownEntry("core/rejection.py", "probs_from_logits",
               static=("temperature",)),
)

#: Param names treated static when traced-ness must be inferred (registry
#: roots and protocol-dispatched methods).  Where an actual call site is
#: visible, flow from the site overrides this list.
ALWAYS_STATIC_PARAMS: FrozenSet[str] = frozenset({
    "self", "cls", "cfg", "config", "tcfg", "dcfg", "target_cfg",
})

#: Attribute reads that are static even on a traced value.
STATIC_RESULT_ATTRS: FrozenSet[str] = frozenset({
    "shape", "dtype", "ndim", "size", "aval", "sharding",
})

#: Calls whose result is static regardless of traced arguments.
STATIC_RESULT_CALLS: FrozenSet[str] = frozenset({
    "len", "isinstance", "issubclass", "hasattr", "getattr", "type",
    "callable", "id", "repr", "range",
})


#: Axis names the repo's meshes can carry (launch/mesh.py builds
#: ("pod","data","model") sub-meshes; docs/distributed.md).  The sharding
#: lint (S401) resolves collective axis names against the enclosing
#: shard_map's spec literals first and falls back to this set when the
#: mesh expression is a runtime value — so a typo'd axis name is caught
#: even where the mesh is not statically known.
KNOWN_MESH_AXES: FrozenSet[str] = frozenset({"pod", "data", "model"})


@dataclass(frozen=True)
class DonationCandidate:
    """A hot-path buffer the ROADMAP expects to be donated eventually.

    ``module``/``qualname`` locate the function that produces or updates
    the buffer, ``param`` names it, ``note`` carries the tracking context
    surfaced in the D602 message.  The donation lint fires D602 at the
    function's def line unless some jit site in the scanned tree donates
    an argument into it — turning "TODO: donate" comments into findings
    the ratchet tracks.
    """
    module: str
    qualname: str
    param: str
    note: str


#: Buffers with acknowledged donation headroom.  Waive the finding inline
#: (with the reason) while the headroom is accepted; delete the entry when
#: the donation lands.
DONATION_CANDIDATES: Tuple[DonationCandidate, ...] = (
    DonationCandidate(
        "models/moe.py", "warm_experts", "layer_params",
        "ROADMAP: warmed expert buffers stay simulation-only until they "
        "are donated to the gmm dispatch"),
)


def lookup_entry(module_rel: str, qualname: str) -> Optional[KnownEntry]:
    """Find the registry entry for ``qualname`` in the module whose
    repo-relative path ends with the entry's ``module`` suffix."""
    for e in KNOWN_ENTRY_POINTS:
        if module_rel.endswith(e.module) and e.qualname == qualname:
            return e
    return None
