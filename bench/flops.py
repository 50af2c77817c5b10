"""Operations and bytes the algorithm needs, counted from shapes.

``cfg`` is a configuration file's ``target`` dict (the published keys of
the model as run).  A FLOP is a multiply or an add: one multiply-add of a
matrix product counts 2.  Norms, softmax, rotary embeddings, biases and
the embedding lookup are left out: they are not matrix products and are
under 0.1% of a token's work at these widths.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def _head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["d_model"] // cfg["num_heads"]


def attn_matmul_params(cfg: dict) -> int:
    """q, k, v and output projection weights of one attention layer."""
    d, hd = cfg["d_model"], _head_dim(cfg)
    return d * hd * (2 * cfg["num_heads"] + 2 * cfg["num_kv_heads"])


def expert_params(cfg: dict) -> int:
    """Gate, up and down weights of one routed expert."""
    return 3 * cfg["d_model"] * cfg["moe_d_ff"]


def ffn_active_params(cfg: dict) -> int:
    """FFN weights one token multiplies through in one layer: the router,
    its top-k experts and the shared expert, or the dense FFN."""
    d = cfg["d_model"]
    if not cfg.get("num_experts"):
        return 3 * d * cfg["d_ff"]
    return (d * cfg["num_experts"]
            + (cfg["num_experts_per_tok"] + cfg.get("num_shared_experts", 0))
            * expert_params(cfg))


def active_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through: per layer the attention
    projections and the FFN's active part; then the output head."""
    per_layer = attn_matmul_params(cfg) + ffn_active_params(cfg)
    return cfg["num_layers"] * per_layer + cfg["vocab_size"] * cfg["d_model"]


def target_flops_per_token(cfg: dict, context: int = 0) -> float:
    """Target FLOPs to produce one token whose forward attends to
    ``context`` positions (itself included): 2 per weight it multiplies
    through, plus 4 * heads * head_dim per attended position and layer
    (q.k and p.v)."""
    attn = 4 * cfg["num_heads"] * _head_dim(cfg) * context * cfg["num_layers"]
    return 2.0 * active_matmul_params(cfg) + attn


def gmm_calls(cfg: dict, rows: int, experts: int, dtype_bytes: int = 2
              ) -> dict:
    """Least work of the ragged expert FFN over ``rows`` routed rows that
    reach ``experts`` distinct experts: the fused gate+up launch and the
    down launch.  Bytes are those experts' weights (the kernel skips an
    empty expert) plus the rows read and written.  Which experts a call
    reaches is the program's to report: no metric reads this until it
    does (PERF.md, section 7)."""
    d, f = cfg["d_model"], cfg["moe_d_ff"]
    b = dtype_bytes
    return {
        "gate_up": {"flops": 2.0 * 2 * rows * d * f,
                    "bytes": float(experts * 2 * d * f * b + rows * d * b
                                   + rows * f * b)},
        "down": {"flops": 2.0 * rows * f * d,
                 "bytes": float(experts * f * d * b + rows * f * b
                                + rows * d * b)},
    }


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline's least time: the larger of compute and memory time."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip, looked up by ``device_kind``; an
    unknown device is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; known: "
                       f"{sorted(table['devices'])}")
    return table["devices"][device_kind]
