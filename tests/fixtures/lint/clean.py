"""Tracer-SAFE idioms the analyzer must NOT flag (false-positive guard).

Every pattern here appears in the real serving stack: static ``.shape``
reads, ``is None`` checks, string-key pytree membership, range() over a
static bound, ref-mutation inside a Pallas-style nested def, a
correctly-keyed compiled-fn cache, an arity/axis-correct shard_map site,
host arrays rebound through a ``_host`` boundary, split-then-consume key
discipline (fold_in on the loop index), and a donate-and-rebind loop.
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def safe(x, n):
    b = int(x.shape[0])                  # .shape is static — no T103
    if n > 2:                            # n is static_argnames — no T101
        x = x + 1.0
    if x is None:                        # identity compare — no T101
        return jnp.zeros((n,))
    mask = jnp.where(x > 0, x, 0.0)
    for i in range(b):                   # static range — no T108
        mask = mask + i
    return mask


fn = jax.jit(safe, static_argnames=("n",))


def outer(x):
    acc = {"v": x}

    def step():
        acc["v"] = acc["v"] * 2.0        # traced base — no T106

    step()
    return acc["v"]


fn2 = jax.jit(outer)


class Cache:
    def __init__(self):
        self._c = {}

    def build(self, m):
        fn = self._c.get(m)
        if fn is None:
            def inner(x):
                return x * m

            fn = jax.jit(inner)
            self._c[m] = fn              # key covers every builder param
        return fn


def _shard_body(a, b):
    return jax.lax.psum(a * b, "data")


def good_shard_site(mesh, a, b):
    f = shard_map(_shard_body, mesh=mesh,
                  in_specs=(P("data"), P("data")), out_specs=P("data"))
    return f(a, b)                           # arity + axis match — no S4xx


class Boundary:
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
        toks = self._host(toks, jnp.int32)   # rebound at the boundary — no S403
        return fn(toks)


def key_discipline(key):
    for i in range(4):
        key, sub = jax.random.split(key)     # rebind parent — no R501
        _ = jax.random.normal(sub, (2,))
    step_key = jax.random.fold_in(key, 1)
    return jax.random.normal(step_key, (2,))


def per_step_fold(key, xs):
    out = []
    for i in range(len(xs)):
        k = jax.random.fold_in(key, i)       # loop-index fold_in — no R504
        out.append(jax.random.normal(k, (2,)))
    return out


def donate_and_rebind(state, batch):
    fn = jax.jit(lambda s, b: s + b, donate_argnums=(0,))
    for _ in range(3):
        state = fn(state, batch)             # rebound each step — no D601
    return state
