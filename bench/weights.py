"""Target weights drawn from the seed, in the layout the serving model
takes (``params["layers"][0]`` stacks every layer on a leading axis).

Every leaf is drawn from its own key, made from the seed, the leaf's path
and the layer index, so one layer can be drawn again alone (the
reference does, layer by layer) and comes out the same.  The whole tree
is drawn in one jitted call on the device, in the dtype it is served in.

Draws: matrices N(0, 1/fan_in), the router in float32; norm scales
1 + 0.1 N(0, 1); q/k/v biases 0.05 N(0, 1); embedding N(0, 1); output
head N(0, 1/d_model).
"""
from __future__ import annotations

import functools
import zlib
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key that keeps all of a seed's bits (seeds may pass 2**32)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _leaf_key(seed: int, path: str, layer: int = 0) -> jax.Array:
    k = jax.random.fold_in(seed_key(seed), zlib.crc32(path.encode()))
    return jax.random.fold_in(k, layer)


def _hd(cfg):
    return cfg.get("head_dim") or cfg["d_model"] // cfg["num_heads"]


def layer_leaves(cfg: dict) -> List[Tuple[str, tuple, str, str]]:
    """(path, shape, dtype, draw) of one layer's leaves: attention, then
    either routed experts (``num_experts`` set; with a shared expert of
    ``num_shared_experts`` expert widths) or one dense FFN of ``d_ff``."""
    d, hd, dt = cfg["d_model"], _hd(cfg), cfg["dtype"]
    hq, hkv = cfg["num_heads"] * hd, cfg["num_kv_heads"] * hd
    out = [("norm1/scale", (d,), dt, "scale"),
           ("mixer/wq", (d, hq), dt, "fan_in"),
           ("mixer/wk", (d, hkv), dt, "fan_in"),
           ("mixer/wv", (d, hkv), dt, "fan_in"),
           ("mixer/wo", (hq, d), dt, "fan_in"),
           ("norm2/scale", (d,), dt, "scale")]
    if cfg.get("qkv_bias"):
        out += [("mixer/bq", (hq,), dt, "bias"), ("mixer/bk", (hkv,), dt, "bias"),
                ("mixer/bv", (hkv,), dt, "bias")]
    E = cfg.get("num_experts") or 0
    if not E:
        f = cfg["d_ff"]
        return out + [("ffn/w_gate", (d, f), dt, "fan_in"),
                      ("ffn/w_up", (d, f), dt, "fan_in"),
                      ("ffn/w_down", (f, d), dt, "fan_in")]
    f = cfg["moe_d_ff"]
    out += [("ffn/router", (d, E), "float32", "fan_in"),
            ("ffn/w_gate", (E, d, f), dt, "fan_in"),
            ("ffn/w_up", (E, d, f), dt, "fan_in"),
            ("ffn/w_down", (E, f, d), dt, "fan_in")]
    fs = f * cfg.get("num_shared_experts", 0)
    if fs:
        out += [("ffn/shared/w_gate", (d, fs), dt, "fan_in"),
                ("ffn/shared/w_up", (d, fs), dt, "fan_in"),
                ("ffn/shared/w_down", (fs, d), dt, "fan_in")]
    return out


def global_leaves(cfg: dict) -> List[Tuple[str, tuple, str, str]]:
    d, V, dt = cfg["d_model"], cfg["vocab_size"], cfg["dtype"]
    out = [("embed/table", (V, d), dt, "embed"),
           ("final_norm/scale", (d,), dt, "scale")]
    if not cfg.get("tie_embeddings"):
        out.append(("head/table", (V, d), dt, "head"))
    return out


def _draw(key, shape, dtype, draw):
    z = jax.random.normal(key, shape, jnp.float32)
    if draw == "fan_in":
        z = z * (1.0 / float(shape[-2]) ** 0.5)
    elif draw == "scale":
        z = 1.0 + 0.1 * z
    elif draw == "bias":
        z = 0.05 * z
    elif draw == "head":
        z = z * (1.0 / float(shape[-1]) ** 0.5)
    return z.astype(dtype)


def _nest(flat: Dict[str, jax.Array]) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return out


def _layer_keys(seed: int, path: str, n: int) -> jax.Array:
    return jnp.stack([_leaf_key(seed, "layers/" + path, i) for i in range(n)])


@functools.partial(jax.jit, static_argnums=(2, 3))
def _draw_tree(layer_keys, global_keys, lay, glo):
    """``lay`` leaves drawn per key row (a stacked layer axis when the
    keys are stacked) and ``glo`` leaves drawn once."""
    flat = {p: _draw(global_keys[p], s, dt, dr) for p, s, dt, dr in glo}
    tree = _nest(flat)
    if lay:
        def one(keys):
            return _nest({p: _draw(keys[p], s, dt, dr)
                          for p, s, dt, dr in lay})
        stacked = next(iter(layer_keys.values())).ndim > 1
        tree["layers"] = [jax.vmap(one)(layer_keys) if stacked
                          else one(layer_keys)]
    return tree


def target_params(cfg: dict, seed: int) -> dict:
    """Every target weight, drawn in one jitted call on the default
    device."""
    lay, glo = tuple(layer_leaves(cfg)), tuple(global_leaves(cfg))
    lk = {p: _layer_keys(seed, p, cfg["num_layers"]) for p, *_ in lay}
    gk = {p: _leaf_key(seed, p) for p, *_ in glo}
    return _draw_tree(lk, gk, lay, glo)


def layer_params(cfg: dict, seed: int, layer: int) -> dict:
    """One layer's weights, the same values ``target_params`` stacks."""
    lay = tuple(layer_leaves(cfg))
    lk = {p: _leaf_key(seed, "layers/" + p, layer) for p, *_ in lay}
    return _draw_tree(lk, {}, lay, ())["layers"][0]


def global_params(cfg: dict, seed: int) -> dict:
    glo = tuple(global_leaves(cfg))
    return _draw_tree({}, {p: _leaf_key(seed, p) for p, *_ in glo}, (), glo)


def same_layout(a, b) -> bool:
    """True when two param trees have the same structure, shapes and
    dtypes (``b`` may be ``jax.eval_shape`` output)."""
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    return ta == tb and all(x.shape == y.shape and x.dtype == y.dtype
                            for x, y in zip(la, lb))
