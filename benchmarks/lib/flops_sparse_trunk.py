"""Operations and bytes a sparse-attention decoder trunk needs (grouped-
query attention behind an indexer that keeps ``topk`` keys a query, every
layer sparse, no shared expert), counted from a configuration file's plain
keys (the catalog's names and its ``sa_config``; expert and vocabulary
counts are what ONE chip of the stated deployment holds).

Conventions as ``lib/flops_hybrid_trunk.py``: multiply-accumulates of matrix
products only, per token of one forward pass, by part; one BYOL step is 8
forward passes of one sequence and recomputed operations do not count
towards a utilization; a KERNEL's roofline counts what it was asked to run,
recomputation included.  The indexer is counted over CAUSAL pairs (it has
to score every one) and the core over SELECTED pairs — the same work
whatever implements it: a core that forms every causal pair and masks reads
low against it by design.
"""
from __future__ import annotations

FORWARDS_PER_TRAIN_SEQUENCE = 8
ARCHS = ("keye_vl2_30b_a3b", "sparse_trunk_tiny")


def applies(conf: dict) -> bool:
    """Whether ``conf`` is a sparse-attention trunk's configuration."""
    return conf.get("arch") in ARCHS


def causal_pairs(seq_len: int) -> int:
    return seq_len * (seq_len + 1) // 2


def selected_pairs(seq_len: int, topk: int) -> int:
    """``sum_t min(t + 1, topk)`` of one sequence."""
    full = min(seq_len, topk)
    return full * (full + 1) // 2 + (seq_len - full) * topk


def index_macs_per_pair(conf: dict) -> float:
    sa = conf["sa_config"]
    return sa["indexer_num_heads"] * sa["indexer_head_dim"]


def core_macs_per_pair(conf: dict) -> float:
    """``Q K^T`` and ``P V``, all query heads."""
    return conf["num_attention_heads"] * 2 * conf["head_dim"]


def forward_macs_per_token(conf: dict, seq_len: int) -> dict:
    """MACs per token by part, summed over the layers built here, routing
    at its nominal rate (``top_k x held / published`` rows per token and
    layer), selection at its exact size."""
    d, layers = conf["hidden_size"], conf["num_hidden_layers"]
    h, hkv, dh = (conf["num_attention_heads"], conf["num_key_value_heads"],
                  conf["head_dim"])
    sa, f = conf["sa_config"], conf["moe_intermediate_size"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    published = conf.get("published", {}).get("num_experts",
                                              conf["num_experts"])
    routed_share = conf["num_experts_per_tok"] * conf["num_experts"] \
        / published
    return {
        # W_q, W_k, W_v, W_o
        "projections": layers * (d * h * dh + 2 * d * hkv * dh + h * dh * d),
        # W_qI, W_kI, W_w
        "index_projections": layers * d * (j * di + di + j),
        "index_scores": layers * index_macs_per_pair(conf)
        * causal_pairs(seq_len) / seq_len,
        "core": layers * core_macs_per_pair(conf)
        * selected_pairs(seq_len, sa["topk"]) / seq_len,
        "routed_experts": layers * routed_share * 3 * d * f,
        "router": layers * d * published,
    }


def forward_flops_per_sequence(conf: dict, seq_len: int) -> float:
    macs = sum(forward_macs_per_token(conf, seq_len).values()) * seq_len
    d, h, p = (conf["hidden_size"], conf["head_latent_size"],
               conf["projection_size"])
    macs += d * h + h * p + p * h + h * p + d * conf["num_classes"]
    return 2.0 * macs


def train_flops_per_sequence(conf: dict, seq_len: int) -> float:
    return FORWARDS_PER_TRAIN_SEQUENCE * forward_flops_per_sequence(
        conf, seq_len)


def tokens_per_pass(conf: dict) -> int:
    """Tokens of one fused forward pass on one chip: both views of the
    per-chip batch."""
    return 2 * conf["per_chip_batch"] * conf["seq_len"]


def _remat(conf: dict) -> bool:
    return conf.get("remat_policy", "none") != "none"


def core_flops(selected_pairs_a_pass: float, conf: dict) -> float:
    """One step's core from the pairs the step's counter says ONE layer's
    fused pass selected: forward 1 (two products a pair), backward 2.5
    (five, the scores recomputed); target, online and — under remat —
    recomputed forward.  (The head-mean probabilities the index loss reads
    are ``Q K^T`` once more, under that loss's scope, not this one.)"""
    passes = (3 if _remat(conf) else 2) + 2.5
    return 2.0 * core_macs_per_pair(conf) * selected_pairs_a_pass \
        * conf["num_hidden_layers"] * passes


def core_bytes(conf: dict) -> float:
    """``q, k, v`` in and ``o`` out once a forward pass (bf16), the backward
    two passes' worth, as ``flops_hybrid_trunk.attention_core_bytes``; and
    ONE byte a causal pair a pass for the selection's mask."""
    h, hkv, dh = (conf["num_attention_heads"], conf["num_key_value_heads"],
                  conf["head_dim"])
    passes = (3 if _remat(conf) else 2) + 2
    per_token = (2 * h + 2 * hkv) * dh * 2
    mask = causal_pairs(conf["seq_len"]) * 2 * conf["per_chip_batch"]
    return (per_token * tokens_per_pass(conf) + mask) \
        * conf["num_hidden_layers"] * passes


def index_flops(causal_pairs_a_pass: float, conf: dict) -> float:
    """One step's index scores from the causal pairs of ONE layer's fused
    pass: forward 1; target, online and recomputed forward; the backward
    (online only) forms the scores again and two products from their
    cotangent: 3."""
    passes = (3 if _remat(conf) else 2) + 3
    return 2.0 * index_macs_per_pair(conf) * causal_pairs_a_pass \
        * conf["num_hidden_layers"] * passes


def index_bytes(causal_pairs_a_pass: float, conf: dict) -> float:
    """The float32 score of every causal pair written once a pass (read
    back by selection and loss, and its cotangent in the backward), the
    projections' inputs and the small heads beside it."""
    sa, d = conf["sa_config"], conf["hidden_size"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    passes = (3 if _remat(conf) else 2) + 2
    per_token = (d + j * di + di + j) * 2
    return (4 * causal_pairs_a_pass + per_token * tokens_per_pass(conf)) \
        * conf["num_hidden_layers"] * passes
