"""A cell's configuration and traffic cut to a size the CPU runs in
seconds (every kernel in Pallas interpret mode)."""
import copy

TINY_TRAFFIC = {
    "kind": "closed_loop", "clients": 4, "requests": 8,
    "prompt": {"median": 12, "sigma": 0.5, "min": 4, "max": 24},
    "output": {"median": 5, "sigma": 0.5, "min": 2, "max": 10},
    "pairing_seed": 0}


#: Qwen2-7B at its published widths (arXiv:2407.10671): the dense model
#: of the paper's MoE-versus-dense comparison, not yet a cell (PERF.md,
#: section 7)
QWEN2_7B = {"name": "qwen2-7b", "family": "dense", "num_layers": 28,
            "d_model": 3584, "num_heads": 28, "num_kv_heads": 4,
            "head_dim": 128, "d_ff": 18944, "vocab_size": 152064,
            "qkv_bias": True, "rope_theta": 1000000.0, "norm_eps": 1e-06,
            "dtype": "bfloat16"}


def dense_tiny_target() -> dict:
    """Qwen2-7B's architecture at a CPU size."""
    return dict(QWEN2_7B, num_layers=2, d_model=64, num_heads=4,
                num_kv_heads=2, head_dim=16, d_ff=96, vocab_size=256)


def tiny_config(name: str, mean_logit_gap: float = 0.05) -> dict:
    """The named configuration at a CPU size: every width cut, the
    architecture (experts, shared expert, biases, proposer) kept."""
    from bench import harness
    c = copy.deepcopy(harness.load_config(name))
    t = c["target"]
    t.update(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
             head_dim=16, d_ff=64, vocab_size=256, moe_d_ff=32)
    if t.get("num_shared_experts"):
        t.update(num_experts=8, num_experts_per_tok=2, num_shared_experts=2)
    else:
        t.update(num_experts=4, num_experts_per_tok=2)
    if "draft" in c:
        c["draft"].update(num_layers=2, d_model=32, num_heads=2,
                          num_kv_heads=1, head_dim=16, d_ff=64,
                          vocab_size=256)
    c["check"] = {"sample": 4, "mean_logit_gap": mean_logit_gap}
    return c
