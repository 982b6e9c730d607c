"""byol_tpu.ops — the in-tree accelerator kernels.

One auditable home for every Pallas kernel the repo ships (the GL109
discipline: kernels live here, each with an ``interpret=`` fallback so CPU
tier-1 runs the real kernel code) plus the shared plumbing in
:mod:`byol_tpu.ops.common`.  The public kernel API is re-exported here so
call sites name the capability, not the file:

- :func:`packed_self_attention` — whole-sequence softmax attention over the
  packed ``qkv``, forward and backward, for sequences that fit VMEM (what
  ``attn_impl='dense'`` runs on a TPU at ViT-B/16's 197 tokens).
- :mod:`byol_tpu.ops.delta_rule` (``within_chunk``, ``applies``) — the
  chunked gated delta rule's within-chunk stage (a ``C x C`` unit
  triangular system a chunk, solved in VMEM), forward and backward: what
  ``models/gated_delta.py`` runs on a TPU at chunks and heads of 128.
- :mod:`byol_tpu.ops.gdn_passes` (``conv_silu``, ``gated_norm``,
  ``applies``) — that layer's two elementwise stages, the depthwise causal
  convolution with its SiLU and the gated RMS norm, each ONE pass over HBM
  forward and one backward, float32 on a few rows in registers: what
  ``models/gated_delta.GatedDeltaNet`` runs on a TPU at channels and heads
  of whole 128-lane tiles.
- :mod:`byol_tpu.ops.causal_attention` (``attend``, ``applies``) — the
  tiled causal core, grouped-query softmax a ``block x block`` tile at a
  time with the tile's squares in VMEM, forward and backward, over every
  causal key or, with ``selected=``, over each query's SELECTED ones: what
  ``ops/attention.blockwise_causal_attention`` and ``selected_attention``
  run on a TPU at blocks of 128 lanes and heads of 64, 128 or 256
  (``models/decoder_trunk``'s grouped-query, latent and sparse attention).
- :mod:`byol_tpu.ops.key_selection` (``search_rows``, ``applies``) — the
  exact top-k search in front of that core: a query block's row of index
  score tiles held in VMEM for the 32 counting passes that build each
  row's threshold, the set written tile by tile with ties from the left:
  what ``select_top_keys`` runs on a TPU at blocks of 128 lanes.
- :mod:`byol_tpu.ops.sum_copies` (``by_token``, ``sum_copies``, ``applies``)
  — the expert layer's combine (and its dispatch's backward) as ONE gather
  of the held rows into token order and a one-hot segment-sum kernel on the
  matrix unit: what ``models/decoder_trunk._sum_copies`` runs on a TPU where
  the sorted window is shorter than every copy.
- :mod:`byol_tpu.ops.expert_routing` (``choose``, ``tables``, ``applies``)
  — the expert layer's routing without a sort: the k choices of a router
  row as k rounds of maximum over rows held in VMEM, the dispatch tables by
  counting and an in-VMEM compression of the ``held x tokens`` membership:
  what ``models/decoder_trunk.ExpertLayer``'s ``route`` runs on a TPU at
  whole tiles of tokens and a router 64 to 512 wide.
- :func:`fused_two_view` — the fused uint8→two-view augmentation
  (``--fused-augment on``): one VMEM pass per image for
  convert/crop/flip/jitter/grayscale, blur as an MXU conv on the output.
"""
from byol_tpu.ops.common import LANES, resolve_interpret
from byol_tpu.ops.fused_augment import crop_weight_mats, fused_two_view
from byol_tpu.ops.packed_attention import packed_self_attention

__all__ = [
    "LANES", "resolve_interpret", "packed_self_attention",
    "crop_weight_mats", "fused_two_view",
]
