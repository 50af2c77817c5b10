"""Ragged + fused grouped-matmul kernels vs the ref.py oracles.

Interpret-mode parity over the adversarial routing shapes the serving path
actually produces — empty experts, fully-imbalanced routing, group sizes
that aren't tile multiples — plus the tile-count assertion that kernel work
scales with the routed token count N·K, not with E·C capacity bins."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.kernels.gmm.ops import gmm, gmm_legacy, moe_ffn_gmm
from repro.kernels.gmm.ragged import (fused_gate_up, make_group_metadata,
                                      ragged_gmm, ragged_moe_ffn)
from repro.kernels.gmm.ref import (fused_gate_up_ref, moe_ffn_ref,
                                   ragged_gmm_ref, ragged_moe_ffn_ref)
from repro.models.moe import init_moe, moe_forward

pytestmark = pytest.mark.tier1

# empty experts / fully-imbalanced / unaligned group sizes / single expert
GROUP_CASES = [
    [5, 0, 11],                        # empty middle expert, tiny N
    [0, 0, 310, 0],                    # all tokens on ONE expert
    [37, 0, 1, 129, 0, 77, 13, 200],   # nothing tile-aligned, two empties
    [256],                             # E=1 degenerate
]


def _case(sizes, D, F, dtype=jnp.float32, seed=0):
    sizes_np = np.asarray(sizes, np.int64)
    E, N = len(sizes_np), int(sizes_np.sum())
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    xs = jax.random.normal(ks[0], (N, D), dtype)
    wg = (jax.random.normal(ks[1], (E, D, F)) / np.sqrt(D)).astype(dtype)
    wu = (jax.random.normal(ks[2], (E, D, F)) / np.sqrt(D)).astype(dtype)
    return jnp.asarray(sizes_np, jnp.int32), xs, wg, wu


def _tol(dtype):
    # bf16 inputs, fp32 accumulation: tolerance sized to bf16 rounding
    return 1e-4 if dtype == jnp.float32 else 3e-2


@pytest.mark.parametrize("sizes", GROUP_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ragged_gmm_matches_ref(sizes, dtype):
    sizes, xs, w, _ = _case(sizes, D=64, F=128, dtype=dtype)
    out = ragged_gmm(xs, w, sizes, interpret=True)
    ref = ragged_gmm_ref(xs, w, sizes)
    tol = _tol(dtype)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("sizes", GROUP_CASES)
@pytest.mark.parametrize("activation", ["silu", "gelu"])
def test_fused_gate_up_matches_ref(sizes, activation):
    sizes, xs, wg, wu = _case(sizes, D=64, F=96)
    out = fused_gate_up(xs, wg, wu, sizes, activation=activation,
                        interpret=True)
    ref = fused_gate_up_ref(xs, wg, wu, sizes, activation)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)


def test_ragged_moe_ffn_matches_ref():
    sizes, xs, wg, wu = _case([37, 0, 1, 129, 0, 77, 13, 200], D=64, F=96)
    wd = jax.random.normal(jax.random.PRNGKey(9),
                           (len(sizes), 96, 64)) / np.sqrt(96)
    out = ragged_moe_ffn(xs, wg, wu, wd, sizes, interpret=True)
    ref = ragged_moe_ffn_ref(xs, wg, wu, wd, sizes)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-4)


def _stacked_case(P=3, D=64, F=96, seed=0):
    """P layers of (E, D, F)/(E, F, D) experts stacked on a leading axis."""
    sizes = jnp.asarray([37, 0, 1, 129, 0, 77, 13, 200], jnp.int32)
    E, N = sizes.shape[0], int(sizes.sum())
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    xs = jax.random.normal(ks[0], (N, D))
    wg = jax.random.normal(ks[1], (P, E, D, F)) / np.sqrt(D)
    wu = jax.random.normal(ks[2], (P, E, D, F)) / np.sqrt(D)
    wd = jax.random.normal(ks[3], (P, E, F, D)) / np.sqrt(F)
    return sizes, xs, wg, wu, wd


# each entry point on (xs, wg, wu, wd, sizes): the weights it takes, and
# its ref.py oracle on per-layer (E, ...) weights
_ENTRY = {
    "ragged_gmm": (ragged_gmm, lambda wg, wu, wd: (wg,),
                   lambda xs, w, s: ragged_gmm_ref(xs, *w, s), 1e-4),
    "fused_gate_up": (fused_gate_up, lambda wg, wu, wd: (wg, wu),
                      lambda xs, w, s: fused_gate_up_ref(xs, *w, s), 1e-4),
    "ragged_moe_ffn": (ragged_moe_ffn, lambda wg, wu, wd: (wg, wu, wd),
                       lambda xs, w, s: ragged_moe_ffn_ref(xs, *w, s), 2e-4),
}


@pytest.mark.parametrize("entry", sorted(_ENTRY))
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_stacked_weights_read_at_layer(entry, layer):
    """Stacked (P, E, ...) weights and a layer index give exactly the call
    on that layer's (E, ...) slice, and still match the oracle."""
    fn, pick, ref, tol = _ENTRY[entry]
    sizes, xs, *ws = _stacked_case()
    stacked = pick(*ws)
    sliced = tuple(w[layer] for w in stacked)
    got = fn(xs, *stacked, sizes, layer=jnp.int32(layer), interpret=True)
    want = fn(xs, *sliced, sizes, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref(xs, sliced, sizes)),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("entry", sorted(_ENTRY))
def test_unstacked_weights_are_one_layer_stack(entry):
    """A 3-D (E, ...) weight needs no layer and is the stack of one layer at
    index 0; a stack without a layer index is refused."""
    fn, pick, _, _ = _ENTRY[entry]
    sizes, xs, *ws = _stacked_case(P=1)
    stacked = pick(*ws)
    plain = fn(xs, *(w[0] for w in stacked), sizes, interpret=True)
    one = fn(xs, *stacked, sizes, layer=0, interpret=True)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(one))
    with pytest.raises(TypeError, match="layer index"):
        fn(xs, *stacked, sizes, interpret=True)


@pytest.mark.parametrize("K", [1, 2, 8])
def test_moe_forward_gmm_parity_across_topk(K):
    """Full routed-FFN parity through moe_forward for K in {1, 2, 8}."""
    cfg = ModelConfig("m", "moe", 2, 64, 4, 2, 128, 256, num_experts=8,
                      num_experts_per_tok=K, moe_d_ff=128, dtype="float32")
    p = init_moe(jax.random.PRNGKey(K), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(K + 10), (2, 33, 64)) * 0.5
    y_ref, _ = moe_forward(p, cfg, x, dispatch="onehot")
    y, _ = moe_forward(p, cfg, x, dispatch="gmm")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-4,
                               atol=2e-4)


# ------------------------------------------------------------- work scaling

def test_tile_count_scales_with_routed_tokens_not_capacity_bins():
    """The metadata's num_visits IS the kernel's m-tile work (padding visits
    are pl.when-skipped): bounded by tiles(N) + boundary straddles, far
    below the E * C/bm tiles the capacity path launches."""
    E, bm = 64, 128
    sizes = np.zeros(E, np.int64)
    sizes[3], sizes[40] = 200, 56                  # N=256 on 2 of 64 experts
    N = int(sizes.sum())
    n_pad = -(-N // bm) * bm
    meta = make_group_metadata(jnp.asarray(sizes), n_pad, bm)
    visits = int(meta.num_visits[0])
    # expert 3 rows [0,200) -> tiles {0,1}; expert 40 rows [200,256) -> {1}
    assert visits == 3
    capacity_tiles = E * (n_pad // bm)             # gmm_capacity grid m-work
    assert visits * 16 <= capacity_tiles
    # work tracks routed tokens: doubling N roughly doubles visits
    sizes2 = sizes * 2
    n_pad2 = -(-int(sizes2.sum()) // bm) * bm
    visits2 = int(make_group_metadata(jnp.asarray(sizes2), n_pad2,
                                      bm).num_visits[0])
    nonempty = int((sizes > 0).sum())
    assert visits2 <= n_pad2 // bm + nonempty     # tiles(2N) + straddles


def test_empty_experts_cost_zero_visits():
    E, bm = 8, 128
    sizes = np.zeros(E, np.int64)
    sizes[2] = 128                                 # one expert, tile-aligned
    meta = make_group_metadata(jnp.asarray(sizes), 128, bm)
    assert int(meta.num_visits[0]) == 1            # 7 empty experts: 0 tiles


# ------------------------------------------------------- legacy + ffn paths

def test_gmm_legacy_matches_ragged():
    sizes, xs, w, _ = _case([37, 0, 1, 129, 0, 77, 13, 200], D=64, F=128)
    out_legacy = gmm_legacy(xs, w, sizes, interpret=True)
    out_ragged = gmm(xs, w, sizes, interpret=True)
    np.testing.assert_allclose(np.asarray(out_legacy), np.asarray(out_ragged),
                               rtol=2e-4, atol=2e-4)


def test_gmm_legacy_capacity_hint():
    """A static capacity bound >= max group size shrinks the bins but stays
    exact."""
    sizes, xs, w, _ = _case([5, 0, 11], D=64, F=128)
    out = gmm_legacy(xs, w, sizes, capacity=16, interpret=True)
    ref = ragged_gmm_ref(xs, w, sizes)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-4)


def test_moe_ffn_gmm_counts_dropped_tokens():
    """Overflow is no longer silent: all tokens on one expert with a tight
    capacity reports exactly the overflow count."""
    N, D, F, E = 6, 32, 48, 2
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(keys[0], (N, D))
    wg = jax.random.normal(keys[1], (E, D, F)) / np.sqrt(D)
    wu = jax.random.normal(keys[2], (E, D, F)) / np.sqrt(D)
    wd = jax.random.normal(keys[3], (E, F, D)) / np.sqrt(F)
    weights = jnp.ones((N, 1))
    idx = jnp.zeros((N, 1), jnp.int32)             # everyone -> expert 0
    _, dropped = moe_ffn_gmm(x, wg, wu, wd, weights, idx, capacity=4,
                             interpret=True, return_dropped=True)
    assert int(dropped) == 2
    y, dropped0 = moe_ffn_gmm(x, wg, wu, wd, weights, idx, capacity=128,
                              interpret=True, return_dropped=True)
    assert int(dropped0) == 0
    ref = moe_ffn_ref(x, wg, wu, wd, weights, idx)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)
