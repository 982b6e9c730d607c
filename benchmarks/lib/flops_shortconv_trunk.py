"""Operations and bytes a short-convolution decoder trunk needs (gated short
convolutions beside plain grouped-query attention in a listed pattern,
leading dense layers, then sigmoid-routed experts with no shared expert),
counted from a configuration file's plain keys (the catalog's names; expert
and vocabulary counts are what ONE chip of the stated deployment holds).

Conventions as ``lib/flops_sparse_trunk.py``: multiply-accumulates of matrix
products only, per token of one forward pass, by part; one BYOL step is 8
forward passes of one sequence and recomputed operations do not count
towards a utilization; a KERNEL's roofline counts what it was asked to run,
recomputation included — the same count whatever implements it.
"""
from __future__ import annotations

FORWARDS_PER_TRAIN_SEQUENCE = 8
ARCHS = ("lfm2_24b_a2b", "shortconv_trunk_tiny")


def applies(conf: dict) -> bool:
    """Whether ``conf`` is a short-convolution trunk's configuration."""
    return conf.get("arch") in ARCHS


def layer_counts(conf: dict) -> tuple:
    """``(convolution, attention, dense, routing)`` layers built here."""
    kinds = conf["layer_types"]
    dense = conf["num_dense_layers"]
    return (kinds.count("conv"), kinds.count("full_attention"), dense,
            len(kinds) - dense)


def head_dim(conf: dict) -> int:
    return conf["hidden_size"] // conf["num_attention_heads"]


def core_macs_per_pair(conf: dict) -> float:
    """``Q K^T`` and ``P V``, all query heads."""
    return conf["num_attention_heads"] * 2 * head_dim(conf)


def forward_macs_per_token(conf: dict, seq_len: int) -> dict:
    """MACs per token by part, summed over the layers built here, routing
    at its nominal rate (``top_k x held / published`` rows per token and
    layer), the core over the causal pairs."""
    d = conf["hidden_size"]
    hkv, dh = conf["num_key_value_heads"], head_dim(conf)
    conv, attn, dense, routing = layer_counts(conf)
    published = conf.get("published", {}).get("num_experts",
                                              conf["num_experts"])
    routed_share = conf["num_experts_per_tok"] * conf["num_experts"] \
        / published
    return {
        # W_in (D -> 3D) and W_out
        "shortconv_projections": conv * 4 * d * d,
        # W_q, W_k, W_v, W_o
        "gqa_projections": attn * (2 * d * d + 2 * d * hkv * dh),
        "gqa_core": attn * core_macs_per_pair(conf) * (seq_len + 1) / 2,
        "dense_ffn": dense * 3 * d * conf["intermediate_size"],
        "routed_experts": routing * routed_share * 3 * d
        * conf["moe_intermediate_size"],
        "router": routing * d * published,
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


def _forwards(conf: dict) -> int:
    """Forward passes of a layer in one step: target, online and — under
    remat — the recomputed one."""
    return 3 if conf.get("remat_policy", "none") != "none" else 2


def conv_core_flops(conf: dict) -> float:
    """Gate, taps, gate: ``2 + 2 K`` operations an element of ``[tokens,
    D]`` a forward; the backward (each product's two cotangents) twice
    that."""
    per_token = (2 + 2 * conf["conv_L_cache"]) * conf["hidden_size"]
    return per_token * tokens_per_pass(conf) * layer_counts(conf)[0] \
        * (_forwards(conf) + 2)


def conv_core_bytes(conf: dict) -> float:
    """A forward reads ``[tokens, 3D]`` and writes ``[tokens, D]`` (bf16);
    the backward reads those ``3D`` again and the output's cotangent and
    writes the cotangent of ``[tokens, 3D]``.  The taps are nothing beside
    them."""
    d = conf["hidden_size"]
    per_token = (4 * d * _forwards(conf) + 7 * d) * 2
    return per_token * tokens_per_pass(conf) * layer_counts(conf)[0]


def core_flops(conf: dict) -> float:
    """The causal half of ``Q K^T`` and ``P V``: forward 1 (two products),
    backward 2.5 (five, the scores recomputed), as
    ``flops_hybrid_trunk.attention_core_flops``."""
    passes = _forwards(conf) + 2.5
    return 2.0 * core_macs_per_pair(conf) * (conf["seq_len"] + 1) / 2 \
        * tokens_per_pass(conf) * layer_counts(conf)[1] * passes


def core_bytes(conf: dict) -> float:
    """``q, k, v`` in and ``o`` out once a forward pass (bf16); the
    backward two forward passes' worth."""
    per_token = (2 * conf["num_attention_heads"]
                 + 2 * conf["num_key_value_heads"]) * head_dim(conf) * 2
    return per_token * tokens_per_pass(conf) * layer_counts(conf)[1] \
        * (_forwards(conf) + 2)
