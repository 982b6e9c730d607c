"""Operations and bytes a block-diffusion decoder trunk needs (grouped-query
attention under the block-diffusion training mask over rows ``[noised |
clean]``, every layer sparse, no shared expert), counted from a
configuration file's plain keys (the catalog's names and the file's
``block_length``; expert and vocabulary counts are what ONE chip of the
stated deployment holds).

Conventions as ``lib/flops_sparse_trunk.py``: multiply-accumulates of matrix
products only, by part; one BYOL step is 8 forward passes of one SAMPLE —
a row of ``2 L`` positions, every one of which the projections and the
experts serve — and recomputed operations do not count towards a
utilization; a KERNEL's roofline counts what it was asked to run,
recomputation included.  The core is counted over the VISIBLE pairs of the
mask, ``L^2 + L b`` a row and head — the same work whatever implements it: a
core that forms whole tiles of 512 (80 of them a row, 20.97 M pairs for
16.79 M visible) reads low against it by design, and a masked-dense one
over the 136 tiles on or under the diagonal lower still.
"""
from __future__ import annotations

FORWARDS_PER_TRAIN_SAMPLE = 8
ARCHS = ("sdar_30b_a3b", "blockdiff_trunk_tiny")


def applies(conf: dict) -> bool:
    """Whether ``conf`` is a block-diffusion trunk's configuration."""
    return conf.get("arch") in ARCHS


def visible_pairs(length: int, block_length: int) -> int:
    """(query, key) pairs one head sees in one row of ``2 length``: clean on
    clean ``(L^2 + L b) / 2``, noised on clean ``(L^2 - L b) / 2``, noised on
    its own noised block ``L b``."""
    return length * length + length * block_length


def core_macs_per_pair(conf: dict) -> float:
    """``Q K^T`` and ``P V``, all query heads."""
    return conf["num_attention_heads"] * 2 * conf["head_dim"]


def forward_macs_per_position(conf: dict, length: int) -> dict:
    """MACs per POSITION of a row (``2 length`` of them) by part, summed over
    the layers built here, routing at its nominal rate (``top_k x held /
    published`` rows per position and layer)."""
    d, layers = conf["hidden_size"], conf["num_hidden_layers"]
    h, hkv, dh = (conf["num_attention_heads"], conf["num_key_value_heads"],
                  conf["head_dim"])
    f = conf["moe_intermediate_size"]
    published = conf.get("published", {}).get("num_experts",
                                              conf["num_experts"])
    routed_share = conf["num_experts_per_tok"] * conf["num_experts"] \
        / published
    return {
        # W_q, W_k, W_v, W_o
        "projections": layers * (d * h * dh + 2 * d * hkv * dh + h * dh * d),
        "core": layers * core_macs_per_pair(conf)
        * visible_pairs(length, conf["block_length"]) / (2 * length),
        "routed_experts": layers * routed_share * 3 * d * f,
        "router": layers * d * published,
    }


def forward_flops_per_sample(conf: dict, length: int) -> float:
    macs = sum(forward_macs_per_position(conf, length).values()) * 2 * length
    d, h, p = (conf["hidden_size"], conf["head_latent_size"],
               conf["projection_size"])
    macs += d * h + h * p + p * h + h * p + d * conf["num_classes"]
    return 2.0 * macs


def train_flops_per_sample(conf: dict, length: int) -> float:
    return FORWARDS_PER_TRAIN_SAMPLE * forward_flops_per_sample(conf, length)


def rows_per_pass(conf: dict) -> int:
    """Rows ``[noised | clean]`` of one fused forward pass on one chip: both
    views of the per-chip batch."""
    return 2 * conf["per_chip_batch"]


def _remat(conf: dict) -> bool:
    return conf.get("remat_policy", "none") != "none"


def core_flops(conf: dict) -> float:
    """One step's core over the visible pairs: forward 1 (two products a
    pair), backward 2.5 (five, the scores recomputed); target, online and —
    under remat — recomputed forward."""
    passes = (3 if _remat(conf) else 2) + 2.5
    pairs = visible_pairs(conf["seq_len"], conf["block_length"]) \
        * rows_per_pass(conf)
    return 2.0 * core_macs_per_pair(conf) * pairs \
        * conf["num_hidden_layers"] * passes


def core_bytes(conf: dict) -> float:
    """``q, k, v`` in and ``o`` out once a forward pass (bf16), the backward
    two passes' worth, as ``flops_sparse_trunk.core_bytes``; the mask is made
    in the kernel and crosses nothing."""
    h, hkv, dh = (conf["num_attention_heads"], conf["num_key_value_heads"],
                  conf["head_dim"])
    passes = (3 if _remat(conf) else 2) + 2
    per_position = (2 * h + 2 * hkv) * dh * 2
    return per_position * 2 * conf["seq_len"] * rows_per_pass(conf) \
        * conf["num_hidden_layers"] * passes
