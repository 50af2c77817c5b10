"""The plain reference and the weight draws it shares with the harness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, harness, weights
from bench_tiny import QWEN2_7B, dense_tiny_target, tiny_config


@pytest.mark.parametrize("name", ["qwen2-57b-a14b.sd", "mixtral-8x7b.sd",
                                  "qwen2-7b"])
def test_draws_match_the_served_layout_at_published_size(name):
    from repro.models.model import Model
    cfg = (QWEN2_7B if name == "qwen2-7b"
           else harness.load_config(name)["target"])
    want = jax.eval_shape(Model(harness.model_config(cfg)).init,
                          jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: weights.target_params(cfg, 3))
    assert weights.same_layout(got, want)


def test_one_layer_drawn_alone_is_the_stacked_layer():
    cfg = tiny_config("qwen2-57b-a14b.sd")["target"]
    seed = 2 ** 35 + 9
    full = weights.target_params(cfg, seed)
    for layer in range(cfg["num_layers"]):
        one = weights.layer_params(cfg, seed, layer)
        for a, b in zip(jax.tree.leaves(full["layers"][0]),
                        jax.tree.leaves(one)):
            assert jnp.array_equal(a[layer], b)
    assert not jnp.array_equal(
        full["embed"]["table"],
        weights.target_params(cfg, seed + 2 ** 32)["embed"]["table"])


@pytest.mark.parametrize("name", ["qwen2-57b-a14b.sd", "mixtral-8x7b.sd",
                                  "qwen2-7b"])
def test_reference_matches_the_program_in_float32(name):
    """The program's own full forward (one-hot MoE, no cache) in float32
    against the reference: the same equations."""
    from repro.models.model import Model
    tiny = (dense_tiny_target() if name == "qwen2-7b"
            else tiny_config(name)["target"])
    cfg = dict(tiny, dtype="float32")
    seed = 41
    params = weights.target_params(cfg, seed)
    tokens = np.random.default_rng(0).integers(0, 256, (2, 20)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = Model(harness.model_config(cfg)).forward_train(
            params, jnp.asarray(tokens))[0]
    ref = check.load_reference("moe_decoder")
    h = ref.final_hidden(cfg, seed, tokens)
    want = jnp.einsum("sld,vd->slv", h, params["head"]["table"],
                      precision=jax.lax.Precision.HIGHEST)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert err < 1e-4, err
    best, at, arg = ref.logit_readings(cfg, seed, h.reshape(-1, cfg["d_model"]),
                                       np.zeros(40, np.int32), block=16)
    flat = np.asarray(want).reshape(40, -1)
    np.testing.assert_allclose(best, flat.max(-1), rtol=1e-5)
    np.testing.assert_allclose(at, flat[:, 0], rtol=1e-5, atol=1e-6)
    assert (arg == flat.argmax(-1)).all()


def test_sample_keeps_the_longest_and_sequences_line_up():
    reqs = [{"prompt": np.arange(5) + i, "output": np.arange(i + 1) + 100}
            for i in range(6)]
    got = check.sample(reqs, 7, 3)
    assert len(got) == 3 and len(got[0]["output"]) == 6
    toks, rows, pos, served = check.sequences(got, 16)
    for r, p, s in zip(rows, pos, served):
        seq = np.concatenate([got[r]["prompt"], got[r]["output"]])
        assert seq[p + 1] == s and toks[r, p] == seq[p]
