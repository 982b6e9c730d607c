"""Operations and bytes of latent attention's causal core (``mla/core``:
models/decoder_trunk.LatentAttention over
ops/attention.blockwise_causal_attention), counted from a configuration
file's plain keys — the same count whatever implements the core.

Conventions as ``gqa.core_roofline``'s (``lib/flops_hybrid_trunk.py``,
``lib/flops_shortconv_trunk.py``): the causal half of ``Q K^T`` — at the key
width ``qk_nope_head_dim + qk_rope_head_dim`` — and of ``P V`` — at
``v_head_dim`` — over every head held and every layer built; forward 1 (two
products), backward 2.5 (five, the scores recomputed); target, online and —
under remat — the recomputed forward; ``q, k, v`` in and ``o`` out once a
pass in bf16, the rotary key ONCE for all heads, the backward two forward
passes' worth.  A KERNEL's roofline counts what it was asked to run,
recomputation included.
"""
from __future__ import annotations

# ``mla/core`` as the device names it: flax puts the module's name (``attn``)
# between the trunk layer's scope and the module's own
SCOPE = "mla/attn/core"


def applies(conf: dict) -> bool:
    """Whether ``conf`` is a latent-attention trunk on ONE residual stream
    (the trunk whose core this file counts)."""
    return "kv_lora_rank" in conf and conf.get("hc_mult", 1) == 1


def core_macs_per_pair(conf: dict) -> float:
    """``Q K^T`` and ``P V``, all heads held."""
    return conf["num_attention_heads"] * (
        conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]
        + conf["v_head_dim"])


def tokens_per_pass(conf: dict) -> int:
    """Tokens of one fused forward pass on one chip: both views of the
    per-chip batch."""
    return 2 * conf["per_chip_batch"] * conf["seq_len"]


def _forwards(conf: dict) -> int:
    """Forward passes of a layer in one step: target, online and — under
    remat — the recomputed one."""
    return 3 if conf.get("remat_policy", "none") != "none" else 2


def core_flops(conf: dict) -> float:
    """A step's operations: a query sees ``(S + 1) / 2`` keys on average."""
    passes = _forwards(conf) + 2.5
    return 2.0 * core_macs_per_pair(conf) * (conf["seq_len"] + 1) / 2 \
        * tokens_per_pass(conf) * conf["num_hidden_layers"] * passes


def core_bytes(conf: dict) -> float:
    """A step's bytes: per token and forward ``q`` (every head's nope and
    rope parts), ``k`` (every head's nope part and ONE rope part), ``v`` and
    ``o``, bf16."""
    heads = conf["num_attention_heads"]
    dn, dr, dv = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                  conf["v_head_dim"])
    per_token = (heads * (dn + dr) + heads * dn + dr + 2 * heads * dv) * 2
    return per_token * tokens_per_pass(conf) * conf["num_hidden_layers"] \
        * (_forwards(conf) + 2)
