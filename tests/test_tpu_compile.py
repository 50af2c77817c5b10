"""The serving kernels compile for a TPU v5e at published widths.

Interpret mode never checks the TPU's tiling rules or its fast-memory
budget; the TPU compiler installed with JAX does, for a chip that is
described (``jax.experimental.topologies``) rather than attached.  Each
test lowers a kernel with ``interpret=False`` for one described v5e chip
(or a 1x4 mesh of them), compiles it, and checks that the Mosaic kernel
is in the program (``tpu_custom_call``) and that the program's memory
fits the chip.  Nothing runs, so nothing here is a timing.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels.decode_attention.ops import paged_decode_attention
from repro.kernels.gmm import ragged
from repro.kernels.gmm.ragged import ragged_moe_ffn
from repro.models.transformer import init_stack, stack_forward

V5E_HBM = 16 * 10**9                  # bytes per chip (Google Cloud, "TPU v5e")
KERNEL_TEMP = 64 * 2**20              # scratch a kernel program may add


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:            # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert "tpu_custom_call" in compiled.as_text()
    assert mem.temp_size_in_bytes <= KERNEL_TEMP, mem
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < V5E_HBM, mem
    return mem


@pytest.mark.parametrize("d,f,e,k", [(3584, 2560, 64, 8),     # qwen2-57b-a14b
                                     (4096, 14336, 8, 2)])    # mixtral-8x7b
def test_ragged_moe_ffn_compiles(one_chip, d, f, e, k):
    rows = 8 * 5 * k                  # one verify pass: 8 rows x gamma+1=5
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    bf = jnp.bfloat16
    mem = _compile(
        lambda x, wg, wu, wd, n: ragged_moe_ffn(x, wg, wu, wd, n,
                                                interpret=False),
        s((rows, d), bf), s((e, d, f), bf), s((e, d, f), bf),
        s((e, f, d), bf), s((e,), jnp.int32))
    assert mem.argument_size_in_bytes >= 3 * e * d * f * 2


def test_layer_scan_passes_expert_stacks_to_gmm(one_chip, monkeypatch):
    """Two qwen2-57b-a14b layers through stack_forward's gmm path, one
    verify pass of 16 rows x gamma+1=5 (640 routed rows): the kernels read
    each layer's experts out of the (P, E, d, f) stacks, so no layer's
    bf16[64, ...] expert slab is sliced into a buffer of its own (1.17 GB
    each, 2.4 GB of temporaries when the scan hands the kernel a slice)."""
    monkeypatch.setattr(ragged, "resolve_interpret", lambda i=None: False)
    ragged_moe_ffn.clear_cache()      # no trace made for the CPU is reused
    cfg = ModelConfig("qwen2-57b-a14b", "moe", 2, 3584, 28, 4, 2560, 151936,
                      head_dim=128, num_experts=64, num_experts_per_tok=8,
                      moe_d_ff=2560, num_shared_experts=8, qkv_bias=True,
                      dtype="bfloat16")
    s = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    lp = jax.tree.map(s, jax.eval_shape(
        lambda: init_stack(jax.random.PRNGKey(0), cfg, jnp.bfloat16)))
    x = s(jax.ShapeDtypeStruct((16, 5, 3584), jnp.bfloat16))
    pos = s(jax.ShapeDtypeStruct((16, 5), jnp.int32))
    compiled = jax.jit(lambda lp, x, pos: stack_forward(
        lp, cfg, x, pos, None, mode="train", dispatch="gmm",
        want_metrics=False)[0]).lower(lp, x, pos).compile()
    ragged_moe_ffn.clear_cache()
    text = compiled.as_text()
    # the launches keep the name the trace's gmm readers select
    assert len(re.findall(r"%ragged_moe_ffn\.\d+ = \S+ custom-call\(",
                          text)) >= 2
    slabs = [ln for ln in text.splitlines()
             if re.search(r"= bf16\[64,\S* fusion\(", ln)
             and "dynamic-slice" in ln]
    assert not slabs, slabs[0][:200]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= KERNEL_TEMP, mem


@pytest.mark.parametrize("hq,hkv", [(28, 4), (32, 8)])
def test_paged_decode_attention_compiles(one_chip, hq, hkv):
    B, T, D, ps, MP = 8, 5, 128, 64, 16
    NP = B * MP + 1
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = s((NP, hkv, ps, D), jnp.bfloat16)
    _compile(
        lambda q, kp, vp, n, t: paged_decode_attention(q, kp, vp, n, t,
                                                       interpret=False),
        s((B, T, hq, D), jnp.bfloat16), pool, pool, s((B,), jnp.int32),
        s((B, MP), jnp.int32))


def test_sharded_paged_decode_attention_compiles(topo):
    """On a 1x4 mesh the kernel runs per shard under shard_map (the TPU
    compiler cannot partition a Mosaic call), KV heads split over
    "model" like the page pools."""
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(1, 4), ("data", "model"))
    B, T, Hq, Hkv, D, ps, MP = 8, 5, 28, 4, 128, 64, 16
    NP = B * MP + 1

    def s(shape, dt, spec):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, spec))

    pool = s((NP, Hkv, ps, D), jnp.bfloat16, P(None, "model", None, None))
    mem = _compile(
        lambda q, kp, vp, n, t: paged_decode_attention(
            q, kp, vp, n, t, interpret=False, mesh=mesh),
        s((B, T, Hq, D), jnp.bfloat16, P(None, None, "model", None)),
        pool, pool, s((B,), jnp.int32, P()), s((B, MP), jnp.int32, P()))
    # each chip holds a quarter of the pools' heads
    assert mem.argument_size_in_bytes < 2 * NP * Hkv * ps * D * 2 / 2
