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
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels.decode_attention.ops import paged_decode_attention
from repro.kernels.gmm.ragged import ragged_moe_ffn

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
