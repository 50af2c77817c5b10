"""Jitted public wrappers around the gmm kernels: capacity dispatch → grouped
matmul → weighted combine, i.e. a full MoE FFN built on the kernel.

Grouped-matmul entry points, fastest first:

  * ``gmm``        — ragged megablox-style kernel (kernels/gmm/ragged.py):
                     no densification, work scales with routed tokens.  The
                     serving default behind ``moe_forward(dispatch="gmm")``.
  * ``gmm_legacy`` — the original bin-to-capacity path kept as a fallback
                     (and as a second oracle): tokens are scattered into
                     fixed ``(E, C)`` bins and run through ``gmm_capacity``.
  * ``moe_ffn_gmm``— capacity-limited full FFN (dispatch → 3 GEMMs →
                     combine); overflow drops are now *counted*, not silent.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.gmm.gmm import gmm_capacity
from repro.kernels.gmm.ragged import (fused_gate_up,  # noqa: F401
                                      make_group_metadata, ragged_gmm,
                                      ragged_moe_ffn)
from repro.kernels.gmm.ref import combine_ref, dispatch_ref


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def expert_capacity(n_tokens: int, k: int, num_experts: int,
                    capacity_factor: float = 2.0, align: int = 128) -> int:
    """Fixed per-expert bin size for the capacity-binned paths.

    Parameters
    ----------
    n_tokens : int
        Tokens entering the router (N).
    k : int
        Experts per token (top-K).
    num_experts : int
        Total experts (E).
    capacity_factor : float
        Headroom over the balanced-routing mean N*K/E; the paper (§3.2)
        assumes balanced routing, so 2x keeps drops negligible (validated
        in tests).
    align : int
        Round the bin size up to this multiple (MXU tile alignment).

    Returns
    -------
    int
        Static per-expert bin capacity C.
    """
    mean = n_tokens * k / num_experts
    return max(align, _round_up(int(mean * capacity_factor), align))


@functools.partial(jax.jit, static_argnames=("capacity", "activation",
                                             "interpret", "return_dropped"))
def moe_ffn_gmm(
    x: jnp.ndarray,            # (N, D)
    w_gate: jnp.ndarray,       # (E, D, F)
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,       # (E, F, D)
    weights: jnp.ndarray,      # (N, K) router weights
    indices: jnp.ndarray,      # (N, K) expert ids
    *,
    capacity: int,
    activation: str = "silu",
    interpret: Optional[bool] = None,
    return_dropped: bool = False,
) -> Union[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Capacity-binned MoE FFN: dispatch → 3 grouped GEMMs → combine.

    Parameters
    ----------
    x : jnp.ndarray
        (N, D) token activations.
    w_gate, w_up : jnp.ndarray
        (E, D, F) per-expert up-projections.
    w_down : jnp.ndarray
        (E, F, D) per-expert down-projection.
    weights, indices : jnp.ndarray
        (N, K) router combine weights and expert ids.
    capacity : int
        Static per-expert bin size (see :func:`expert_capacity`).
    activation : str
        "silu" (default) or "gelu".
    interpret : bool, optional
        Run the Pallas kernels in interpret mode; default: decided from
        the backend (``repro.kernels.resolve_interpret``).
    return_dropped : bool
        Also return the number of (token, k) assignments that overflowed
        their expert's bin — deterministic (slot order) but no longer
        silent.

    Returns
    -------
    jnp.ndarray or (jnp.ndarray, jnp.ndarray)
        (N, D) combined output; with ``return_dropped=True`` also the int32
        overflow count.
    """
    E, D, F = w_gate.shape
    N = x.shape[0]
    interpret = resolve_interpret(interpret)
    bins, slot, kept = dispatch_ref(x, indices, E, capacity)
    h_gate = gmm_capacity(bins, w_gate, interpret=interpret)
    h_up = gmm_capacity(bins, w_up, interpret=interpret)
    act = jax.nn.gelu if activation == "gelu" else jax.nn.silu
    h = (act(h_gate.astype(jnp.float32)) * h_up.astype(jnp.float32)).astype(x.dtype)
    y_bins = gmm_capacity(h, w_down, interpret=interpret)
    y = combine_ref(y_bins, indices, weights, slot, kept)
    if return_dropped:
        return y, jnp.sum(~kept).astype(jnp.int32)
    return y


def gmm(xs: jnp.ndarray, w: jnp.ndarray, group_sizes: jnp.ndarray,
        *, interpret: Optional[bool] = None) -> jnp.ndarray:
    """Sorted-token grouped matmul via the ragged Pallas kernel.

    Parameters
    ----------
    xs : jnp.ndarray
        (N_sorted, D) token rows sorted by expert id.
    w : jnp.ndarray
        (E, D, F) per-expert weight matrices.
    group_sizes : jnp.ndarray
        (E,) tokens routed to each expert (sums to N_sorted).
    interpret : bool, optional
        Run the kernel in interpret mode; default: decided from the
        backend (``repro.kernels.resolve_interpret``).

    Returns
    -------
    jnp.ndarray
        (N_sorted, F) per-row ``xs[i] @ w[expert_of(i)]``.

    Notes
    -----
    Per-expert offsets are scalar-prefetched and each m-tile looks up its
    expert from the group boundaries — no ``(E, C)`` densification, empty
    experts cost zero tiles, work scales with the routed token count
    (kernels/gmm/ragged.py; tradeoffs in docs/dispatch.md).
    """
    return ragged_gmm(xs, w, group_sizes, interpret=interpret)


def gmm_legacy(xs: jnp.ndarray, w: jnp.ndarray, group_sizes: jnp.ndarray,
               *, capacity: Optional[int] = None,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """Bin-to-capacity fallback for the ragged kernel.

    Parameters
    ----------
    xs : jnp.ndarray
        (N_sorted, D) token rows sorted by expert id.
    w : jnp.ndarray
        (E, D, F) per-expert weight matrices.
    group_sizes : jnp.ndarray
        (E,) tokens routed to each expert.
    capacity : int, optional
        Static bound on the largest group.  Defaults to
        ``round_up(N, 128)`` — exact for any routing, at worst-case cost;
        callers with a tighter guarantee (e.g. a capacity factor) pass it
        to shrink the bins.
    interpret : bool, optional
        Run the kernel in interpret mode; default: decided from the
        backend (``repro.kernels.resolve_interpret``).

    Returns
    -------
    jnp.ndarray
        (N_sorted, F) per-row grouped matmul output.

    Notes
    -----
    Tokens are scattered into fixed-size per-expert bins and run through
    the dense ``gmm_capacity`` kernel.  The ``capacity`` bound is NOT
    checked: a group larger than ``capacity`` has its overflow rows' inputs
    dropped by the scatter and the gather-back clamps their slot to
    ``capacity - 1``, so those output rows silently receive another token's
    result — only pass a capacity you can guarantee.
    """
    E, D, F = w.shape
    N = xs.shape[0]
    C = _round_up(max(int(N), 1), 128) if capacity is None \
        else _round_up(max(int(capacity), 1), 128)
    ends = jnp.cumsum(group_sizes)                              # (E,) once
    offsets = ends - group_sizes
    row = jnp.arange(N)
    expert_of_row = jnp.searchsorted(ends, row, side="right")
    slot_of_row = row - offsets[expert_of_row]
    bins = jnp.zeros((E, C, D), xs.dtype).at[expert_of_row, slot_of_row].set(xs)
    y = gmm_capacity(bins, w, interpret=resolve_interpret(interpret))
    return y[expert_of_row, slot_of_row]
