"""Operations and bytes a decoder-hybrid-decoder trunk needs (Mamba,
differential attention under a band, in full and as cross attention, gated
memory units, every layer a dense SwiGLU), counted from a configuration
file's plain keys (the catalog's names, the file's ``kept_layers`` and the
sizes it lists as assumed; the vocabulary is what ONE chip of the stated
deployment holds).

Conventions as ``lib/flops_blockdiff_trunk.py``: multiply-accumulates of
matrix products only, by part; one BYOL step is 8 forward passes of one
SAMPLE and recomputed operations do not count towards a utilization — there
a core is counted over the VISIBLE pairs of its rule; a KERNEL's roofline
counts what it was asked to run, recomputation included, the cores over the
tiles FORMED (whole tiles of 512: the band's 31 of the triangle's 136).  The
selective scan has NO matrix product: it counts as bytes — what the kernel
pair must move — and, beside them, as state updates (a state element a
step: one ``exp`` and five multiply-adds forward).
"""
from __future__ import annotations

from benchmarks.lib.reference_sambay_trunk import role

FORWARDS_PER_TRAIN_SAMPLE = 8
ARCHS = ("phi4_mini_flash", "sambay_tiny")
TILE = 512           # keys a tile of the program's blockwise core
SCAN_CHUNK = 128     # steps between two border states of the scan's kernels
# operations a state element and step, by the kernels' own bodies
# (byol_tpu/ops/selective_scan.py): forward exp + 6, backward 2 exp + 22
SCAN_OPS = {"forward": 7, "backward": 24}


def applies(conf: dict) -> bool:
    """Whether ``conf`` is a decoder-hybrid-decoder trunk's configuration."""
    return conf.get("arch") in ARCHS


def roles(conf: dict) -> list:
    """The kept layers' roles, by their published index."""
    published = conf.get("published", {}).get("num_hidden_layers",
                                              conf["num_hidden_layers"])
    first, last = conf["kept_layers"]
    return [role(i, published, conf["mb_per_layer"])
            for i in range(first, last + 1)]


def inner(conf: dict) -> int:
    return conf["expand"] * conf["hidden_size"]


def visible_pairs(length: int, window: int = 0) -> int:
    """(query, key) pairs a softmax sees in one row: the triangle, or under
    a band the ``window`` latest keys of every query."""
    if not window or window >= length:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def formed_tiles(length: int, window: int = 0, tile: int = TILE) -> int:
    """Tiles of ``tile`` x ``tile`` the blockwise core forms in one row."""
    blocks = -(-length // tile)
    if not window or window >= length:
        return blocks * (blocks + 1) // 2
    back = (window + tile - 2) // tile
    return sum(min(i, back) + 1 for i in range(blocks))


def core_macs_per_pair(conf: dict, backward: bool = False) -> float:
    """Both softmaxes of every query pair: ``Q K^T`` (a head deep) and ``P
    V`` (two heads wide); backward five products, the scores recomputed."""
    dh = conf["head_dim"]
    each = 3 * dh + 2 * 2 * dh if backward else dh + 2 * dh
    return conf["num_attention_heads"] * each


def _windows(conf: dict) -> list:
    """The window (0 = none) of every kept layer with a core."""
    return [conf["sliding_window"] if kind == "band" else 0
            for kind in roles(conf) if kind in ("band", "full", "cross")]


def forward_macs_per_position(conf: dict, length: int) -> dict:
    """MACs per position by part, summed over the layers built here."""
    d, f, width = conf["hidden_size"], conf["intermediate_size"], inner(conf)
    h, hkv, dh = (conf["num_attention_heads"], conf["num_key_value_heads"],
                  conf["head_dim"])
    low = conf["dt_rank"] + 2 * conf["d_state"]
    projections = {
        "ssm": d * 2 * width + width * low + conf["dt_rank"] * width
        + width * d,
        "band": d * (h + 2 * hkv) * dh + h * dh * d,
        "full": d * (h + 2 * hkv) * dh + h * dh * d,
        "cross": 2 * d * h * dh,
        "gmu": 2 * d * width}
    kinds = roles(conf)
    return {
        "ffn": len(kinds) * 3 * d * f,
        "projections": sum(projections[kind] for kind in kinds),
        "cores": core_macs_per_pair(conf) * sum(
            visible_pairs(length, w) for w in _windows(conf)) / length}


def forward_flops_per_sample(conf: dict, length: int) -> float:
    macs = sum(forward_macs_per_position(conf, length).values()) * length
    d, h, p = (conf["hidden_size"], conf["head_latent_size"],
               conf["projection_size"])
    macs += d * h + h * p + p * h + h * p + d * conf["num_classes"]
    return 2.0 * macs


def train_flops_per_sample(conf: dict, length: int) -> float:
    return FORWARDS_PER_TRAIN_SAMPLE * forward_flops_per_sample(conf, length)


def rows_per_pass(conf: dict) -> int:
    """Rows of one fused forward pass on one chip: both views of the
    per-chip batch."""
    return 2 * conf["per_chip_batch"]


def _forwards(conf: dict) -> int:
    """Target, online and — under remat — recomputed forward."""
    return 3 if conf.get("remat_policy", "none") != "none" else 2


def band_tile_share(conf: dict) -> float:
    """Percent of the triangle's tiles a band layer's core forms."""
    length = conf["seq_len"]
    return 100.0 * formed_tiles(length, conf["sliding_window"]) \
        / formed_tiles(length)


def core_flops(conf: dict) -> float:
    """One step's cores over the tiles FORMED, every pass."""
    formed = sum(formed_tiles(conf["seq_len"], w) for w in _windows(conf)) \
        * TILE * TILE * rows_per_pass(conf)
    return 2.0 * formed * (_forwards(conf) * core_macs_per_pair(conf)
                           + core_macs_per_pair(conf, backward=True))


def core_bytes(conf: dict) -> float:
    """``q, k, v`` in and ``o`` out once a forward pass (bf16; the value
    heads as the kernel reads them, once a softmax), the backward two
    passes' worth."""
    h, hkv, dh = (conf["num_attention_heads"], conf["num_key_value_heads"],
                  conf["head_dim"])
    per_position = (h * dh + hkv * dh + hkv * 2 * dh + h * 2 * dh) * 2
    return per_position * conf["seq_len"] * rows_per_pass(conf) \
        * len(_windows(conf)) * (_forwards(conf) + 2)


def scan_elements(conf: dict) -> float:
    """State elements times steps of one pass over one step's rows, all the
    kept Mamba layers."""
    return float(roles(conf).count("ssm") * rows_per_pass(conf)
                 * conf["seq_len"] * inner(conf) * conf["d_state"])


def scan_ops(conf: dict) -> float:
    """The scan kernels' elementwise operations a step, every pass."""
    return scan_elements(conf) * (_forwards(conf) * SCAN_OPS["forward"]
                                  + SCAN_OPS["backward"])


def scan_bytes(conf: dict) -> float:
    """What the scan's kernel pair MUST move a step, float32: forward ``c``
    and ``delta`` in and ``m`` out (a value a channel and position), ``B``
    and ``C`` in (a value a state index and position) and, where a backward
    follows, a border state a chunk out; backward those five in again with
    ``m``'s cotangent, and the cotangents of ``c``, ``delta``, ``B`` and
    ``C`` out.  (The kernels read ``B`` and ``C`` broadcast to 128 lanes:
    more than they must.)"""
    positions = rows_per_pass(conf) * conf["seq_len"]
    wide, narrow = 4.0 * positions * inner(conf), 4.0 * positions \
        * conf["d_state"]
    borders = wide * conf["d_state"] / SCAN_CHUNK
    forward = 3 * wide + 2 * narrow
    kept = _forwards(conf) - 1          # online and recomputed keep borders
    backward = 5 * wide + 4 * narrow + borders
    return roles(conf).count("ssm") * (
        _forwards(conf) * forward + kept * borders + backward)
