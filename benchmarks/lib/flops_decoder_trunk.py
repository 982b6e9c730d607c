"""Operations and bytes a decoder trunk needs, counted from a configuration
file's plain keys (the catalog's names; head, expert and vocabulary counts
are what ONE chip of the stated deployment holds).

Multiply-accumulates of matrix products only (norms, activations, the
rotary embedding, the softmax and the elementwise mixing of the residual
streams are not counted), per token of one forward pass, by part.  One BYOL
step forwards both views through the online and the target network and
back-propagates the online pass: 8 forward passes of one sequence
(``lib/flops.py``'s convention); recomputed operations (remat) do not count
towards a utilization.  A KERNEL's roofline counts what the kernel was
asked to run, recomputation included: ``passes`` below.
"""
from __future__ import annotations

FORWARDS_PER_TRAIN_SEQUENCE = 8


def layer_counts(conf: dict):
    dense = conf["first_k_dense_replace"]
    return dense, conf["num_hidden_layers"] - dense


def forward_macs_per_token(conf: dict, seq_len: int) -> dict:
    """MACs per token by part, summed over the layers built here, routing
    at its nominal rate (``top_k x held / published`` rows per token and
    expert layer)."""
    d, heads = conf["hidden_size"], conf["num_attention_heads"]
    dn, dr, dv = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                  conf["v_head_dim"])
    dense, sparse = layer_counts(conf)
    layers = dense + sparse
    n = conf["hc_mult"]
    f = conf["moe_intermediate_size"]
    published = conf.get("published", {}).get(
        "n_routed_experts", conf["n_routed_experts"])
    routed_share = conf["num_experts_per_tok"] * conf["n_routed_experts"] \
        / published
    return {
        "mla_projections": layers * (
            d * conf["q_lora_rank"]
            + conf["q_lora_rank"] * heads * (dn + dr)
            + d * (conf["kv_lora_rank"] + dr)
            + conf["kv_lora_rank"] * heads * (dn + dv)
            + heads * dv * d),
        # causal: a query sees (S + 1) / 2 keys on average
        "attention_core": layers * heads * (seq_len + 1) / 2
        * (dn + dr + dv),
        "dense_ffn": dense * 3 * d * conf["intermediate_size"],
        "routed_experts": sparse * routed_share * 3 * d * f,
        "shared_expert": sparse * conf["n_shared_experts"] * 3 * d * f,
        "router": sparse * d * published,
        "stream_maps": 2 * layers * n * d * (2 * n + n * n),
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


def expert_passes(conf: dict) -> int:
    """Forward-equivalents the held experts' products run in one step, per
    routed row: target and online forward, the backward (two), and the
    recomputed forward where the layer is rematerialised."""
    return 5 if conf.get("remat_policy", "none") != "none" else 4


def expert_matmul_flops(rows: float, conf: dict) -> float:
    """``rows``: rows routed to held experts in one forward pass of a step,
    summed over the layers that route (the step's counter)."""
    per_row = 2.0 * 3 * conf["hidden_size"] * conf["moe_intermediate_size"]
    return rows * per_row * expert_passes(conf)


def expert_matmul_bytes(rows: float, conf: dict) -> float:
    """The held experts' three bf16 matrices once per pass and layer, plus
    every routed row in and out (bf16)."""
    _, sparse = layer_counts(conf)
    d, f = conf["hidden_size"], conf["moe_intermediate_size"]
    weights = sparse * conf["n_routed_experts"] * 3 * d * f * 2
    return expert_passes(conf) * (weights + rows * 2 * d * 2)
