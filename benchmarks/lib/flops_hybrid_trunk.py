"""Operations and bytes a patterned decoder trunk needs (Gated DeltaNet
layers, a gated grouped-query attention layer every
``full_attention_interval``-th, every layer sparse), counted from a
configuration file's plain keys (the catalog's names; expert and vocabulary
counts are what ONE chip of the stated deployment holds).

Conventions as ``lib/flops_decoder_trunk.py``: multiply-accumulates of
matrix products only, per token of one forward pass, by part; one BYOL step
is 8 forward passes of one sequence and recomputed operations do not count
towards a utilization; a KERNEL's roofline counts what it was asked to run,
recomputation included.
"""
from __future__ import annotations

RULE_CHUNK = 64      # the published code's; counted whatever the program uses
FORWARDS_PER_TRAIN_SEQUENCE = 8


def layer_counts(conf: dict):
    """``(Gated DeltaNet layers, gated attention layers)`` built here."""
    layers, every = conf["num_hidden_layers"], conf["full_attention_interval"]
    attention = sum((i + 1) % every == 0 for i in range(layers))
    return layers - attention, attention


def delta_rule_macs_per_token(conf: dict) -> float:
    """The chunked (WY) form of the gated delta rule at chunk ``C`` = 64,
    per token, all value heads of one layer.  Per chunk and head:

    * ``beta K K^T`` (the triangular system's matrix): ``C^2 d_k``
    * its inverse by forward substitution, row ``i`` against the ``i`` rows
      above it: ``sum i^2 = C^3 / 3``
    * ``u = T (beta V)``: ``C^2 d_v``;  ``w = T (beta K e^gamma)``: ``C^2 d_k``
    * ``Q K^T`` inside the chunk: ``C^2 d_k``
    * against the carried state: ``w S`` and ``q S``: ``2 C d_k d_v``; the
      chunk's own ``(Q K^T) delta``: ``C^2 d_v``; the state's update ``K^T
      delta``: ``C d_k d_v``
    """
    c, dk, dv = RULE_CHUNK, conf["linear_key_head_dim"], \
        conf["linear_value_head_dim"]
    per_chunk = (c * c * dk + c ** 3 / 3 + c * c * dv + c * c * dk
                 + c * c * dk + 2 * c * dk * dv + c * c * dv + c * dk * dv)
    return conf["linear_num_value_heads"] * per_chunk / c


def attention_core_macs_per_token(conf: dict, seq_len: int) -> float:
    """The causal half of ``Q K^T`` and of ``P V``: a query sees ``(S + 1)
    / 2`` keys on average."""
    return conf["num_attention_heads"] * (seq_len + 1) / 2 \
        * 2 * conf["head_dim"]


def forward_macs_per_token(conf: dict, seq_len: int) -> dict:
    """MACs per token by part, summed over the layers built here, routing
    at its nominal rate (``top_k x held / published`` rows per token and
    layer)."""
    d = conf["hidden_size"]
    hk, hv = conf["linear_num_key_heads"], conf["linear_num_value_heads"]
    dk, dv = conf["linear_key_head_dim"], conf["linear_value_head_dim"]
    h, hkv, dh = (conf["num_attention_heads"], conf["num_key_value_heads"],
                  conf["head_dim"])
    gdn, gqa = layer_counts(conf)
    layers, f = gdn + gqa, conf["moe_intermediate_size"]
    published = conf.get("published", {}).get("num_experts",
                                              conf["num_experts"])
    routed_share = conf["num_experts_per_tok"] * conf["num_experts"] \
        / published
    return {
        # W_qkvz, W_ba, the 4-tap convolution, W_o
        "gdn_projections": gdn * (
            d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv
            + conf["linear_conv_kernel_dim"] * (2 * hk * dk + hv * dv)
            + hv * dv * d),
        "gdn_rule": gdn * delta_rule_macs_per_token(conf),
        # W_q (query and gate), W_k, W_v, W_o
        "gqa_projections": gqa * (d * h * dh * 2 + 2 * d * hkv * dh
                                  + h * dh * d),
        "gqa_core": gqa * attention_core_macs_per_token(conf, seq_len),
        "routed_experts": layers * routed_share * 3 * d * f,
        "shared_expert": layers * (
            3 * d * conf["shared_expert_intermediate_size"] + d),
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


def delta_rule_flops(conf: dict) -> float:
    """One step's rule, all DeltaNet layers: target and online forward, the
    backward (two forward-equivalents) and the recomputed forward where the
    layer is rematerialised."""
    passes = 5 if _remat(conf) else 4
    return 2.0 * delta_rule_macs_per_token(conf) * tokens_per_pass(conf) \
        * layer_counts(conf)[0] * passes


def delta_rule_bytes(conf: dict) -> float:
    """``q, k`` (per key head), ``v`` in and ``o`` out in bf16, ``g`` and
    ``beta`` in float32, once a pass, plus one float32 state per chunk
    boundary and value head; the same five (or four) passes."""
    hk, hv = conf["linear_num_key_heads"], conf["linear_num_value_heads"]
    dk, dv = conf["linear_key_head_dim"], conf["linear_value_head_dim"]
    per_token = (2 * hk * dk * 2 + 2 * hv * dv * 2 + 2 * hv * 4
                 + hv * dk * dv * 4 / RULE_CHUNK)
    passes = 5 if _remat(conf) else 4
    return per_token * tokens_per_pass(conf) * layer_counts(conf)[0] * passes


def attention_core_flops(conf: dict) -> float:
    """Forward 1 (two products), backward 2.5 (five, the scores recomputed);
    target, online and — under remat — recomputed forward."""
    passes = (3 if _remat(conf) else 2) + 2.5
    return 2.0 * attention_core_macs_per_token(conf, conf["seq_len"]) \
        * tokens_per_pass(conf) * layer_counts(conf)[1] * passes


def attention_core_bytes(conf: dict) -> float:
    """``q, k, v`` in and ``o`` out once a forward pass (bf16); the
    backward reads those and ``dO`` and writes ``dq, dk, dv``: two forward
    passes' worth."""
    h, hkv, dh = (conf["num_attention_heads"], conf["num_key_value_heads"],
                  conf["head_dim"])
    per_token = (2 * h + 2 * hkv) * dh * 2
    passes = (3 if _remat(conf) else 2) + 2
    return per_token * tokens_per_pass(conf) * layer_counts(conf)[1] * passes
