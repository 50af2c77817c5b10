"""Pallas TPU kernels: ragged grouped matmul, decode/verify attention
(dense and paged) and flash attention, each with a jnp oracle in ref.py."""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Whether a kernel call runs in Pallas interpret mode.

    An explicit ``interpret`` wins.  Otherwise the decision is made at call
    time from the backend that will run the call: the TPU compiles the
    kernel, the CPU interprets it (tests), and any other backend is an
    error.  Deciding here rather than at import keeps importing ``repro``
    from initialising a backend, and keeps a machine whose TPU failed to
    come up from quietly serving through the interpreter.
    """
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"no Pallas TPU kernel path on backend {backend!r}")
