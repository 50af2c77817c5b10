"""Sharding/collective contract violations the S4xx pass must flag.

Self-contained: carries its own ``cache_spec`` definition so the S404
placement-rule check resolves patterns without importing the real
``distributed/sharding`` module.
"""
import re

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def cache_spec(path):
    if re.search(r"pages/table$", path):
        return P(None)
    if re.search(r"(k|v)_pages$", path):
        return P("model", None)
    return P("data")


def _body(a, b):
    y = jax.lax.psum(a, "tensor")
    return y + b


def bad_axis_and_arity(mesh, a, b):
    f = shard_map(_body, mesh=mesh,
                  in_specs=(P("data"), P("data"), P("data")),
                  out_specs=P("data"))
    return f(a, b)


def _body_pair(a):
    return a, a


def bad_out_arity(mesh, a):
    f = shard_map(_body_pair, mesh=mesh, in_specs=(P("data"),),
                  out_specs=(P("data"), P("data"), P("data")))
    return f(a)


class Engine:
    def __init__(self):
        self._c = {}

    def _host(self, x, dt):
        return jnp.asarray(x, dt)

    def _build(self):
        fn = self._c.get("step")
        if fn is None:
            fn = jax.jit(lambda t: t + 1)
            self._c["step"] = fn
        return fn

    def step(self):
        fn = self._build()
        toks = np.zeros((4,), np.int32)
        return fn(toks)


def init_cache(pages, page_size):
    return {"k_pages": jnp.zeros((1, pages, page_size, 1, 4)),
            "q_pages": jnp.zeros((1, pages, page_size, 1, 4))}


def lookup_rule():
    return cache_spec("layers/0/q_pages")


def misconfigure(mesh):
    from repro.distributed.constraints import set_mesh
    set_mesh(mesh)
