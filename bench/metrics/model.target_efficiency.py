"""The paper's target efficiency eta = T_T(B, 1) / T_T(B, gamma + 1): the
target's extend at 1 and at gamma + 1 tokens per row, B = the cell's
clients, on a paged cache prefilled with the prompts of the window's
first B finished requests whose answers have gamma + 1 tokens or more.
The extended tokens are those requests' first served tokens, so every
row verifies distinct real tokens, as it would with drafts that are
accepted (host clock around 20 back-to-back calls ending in
block_until_ready; median of 5 such blocks).  Only SD cells."""
import time

import numpy as np

CALLS, BLOCKS = 20, 5


def _time(fn, *args):
    import jax
    jax.block_until_ready(fn(*args))          # compile or load, warm
    out = []
    for _ in range(BLOCKS):
        t0 = time.perf_counter()
        for _ in range(CALLS):
            r = fn(*args)
        jax.block_until_ready(r)
        out.append((time.perf_counter() - t0) / CALLS)
    return float(np.median(out))


def read(ctx):
    import jax
    import jax.numpy as jnp
    from bench.harness import CLEAN
    from repro.models.model import PageAllocator
    gamma = ctx.config["gamma"]
    if gamma <= 0:
        return None
    B, ps = ctx.traffic["clients"], ctx.config["serving"]["page_size"]
    reqs = [r for r in ctx.streams[0]["requests"]
            if r["reason"] in CLEAN and len(r["output"]) > gamma][:B]
    if len(reqs) < B:
        return None
    eng = ctx.engine
    model, params = eng.target, eng.params_t
    T = max(len(r["prompt"]) for r in reqs)
    toks = np.zeros((B, T), np.int32)
    for i, r in enumerate(reqs):
        toks[i, :len(r["prompt"])] = r["prompt"]
    lengths = np.array([len(r["prompt"]) for r in reqs], np.int32)
    served = np.stack([r["output"][:gamma + 1] for r in reqs]).astype(np.int32)
    max_seq = -(-(T + gamma + 1) // ps) * ps
    alloc = PageAllocator(B, ps, B * (max_seq // ps) + 1, max_seq // ps)
    for b in range(B):
        alloc.alloc(b, max_seq)
    cache = model.init_cache(B, max_seq, paged=True, page_size=ps,
                             pool_pages=alloc.pool_pages)
    cache["pages"]["table"] = jnp.asarray(alloc.table)
    _, cache = jax.jit(model.prefill)(params, jnp.asarray(toks), cache,
                                      lengths=jnp.asarray(lengths))
    ext = jax.jit(lambda p, t, c: model.extend(p, t, c)[0])
    t1 = _time(ext, params, jnp.asarray(served[:, :1]), cache)
    tg = _time(ext, params, jnp.asarray(served), cache)
    ctx.note(f"target extend at B={B}: T=1 {t1 * 1e3:.4f} ms, "
             f"T={gamma + 1} {tg * 1e3:.4f} ms")
    return t1 / tg
