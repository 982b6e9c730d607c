"""Ring attention — sequence/context parallelism over the mesh.

Long-context support is first-class in this framework (the reference has no
attention and no sequence dimension at all — SURVEY.md §5.7 records this as
a capability extension, not parity).  When a sequence is sharded over the
``sequence`` mesh axis, no device ever holds the full K/V: each device keeps
its local K/V block and the blocks ROTATE around the ring via
``lax.ppermute`` (ICI neighbor exchange), while every device folds each
visiting block into an online-softmax accumulator for its local queries.

Per device: compute O(S_local * S) , memory O(S_local * D) — the S x S
matrix never exists anywhere, and the ppermute transfer of the next block
overlaps with the matmul of the current one (XLA schedules the ICI send
alongside the MXU work).

Two entry points:
- :func:`ring_attention_local` — the per-shard body; call it inside an
  existing ``shard_map`` with the ``sequence`` axis in scope;
- :func:`ring_attention` — self-contained: wraps itself in ``shard_map``
  over the ambient mesh (usable as a drop-in ``attn_impl`` inside jit).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from byol_tpu.ops import common as ops_common
from byol_tpu.ops.common import MASKED
from byol_tpu.parallel.mesh import (DATA_AXIS, SEQUENCE_AXIS,
                                    ambient_mesh)

def ring_attention_local(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         axis_name: str = SEQUENCE_AXIS) -> jnp.ndarray:
    """Per-shard ring attention body.

    q, k, v: (B, H, S_local, D) — this device's sequence shard.  Must run
    where ``axis_name`` is bound (inside shard_map).  Returns the attention
    output for the local queries over the GLOBAL (ring-assembled) K/V.
    """
    n = jax.lax.psum(1, axis_name)
    scale = q.shape[-1] ** -0.5
    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(_, carry):
        m, l, acc, k_cur, v_cur = carry
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k_cur) * scale
        s = s.astype(jnp.float32)
        m_curr = jnp.max(s, axis=-1, keepdims=True)
        m_next = jnp.maximum(m, m_curr)
        p = jnp.exp(s - m_next)
        alpha = jnp.exp(m - m_next)
        l_next = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v_cur.dtype),
                        v_cur).astype(jnp.float32)
        # rotate K/V to the next device; overlaps with next iteration's math
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return m_next, l_next, acc * alpha + pv, k_nxt, v_nxt

    b, h, s_loc, d = q.shape
    m0 = jnp.full((b, h, s_loc, 1), MASKED, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc, 1), jnp.float32)
    acc0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    _, l, acc, _, _ = jax.lax.fori_loop(
        0, n, step, (m0, l0, acc0, k, v))
    return (acc / l).astype(q.dtype)


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                   mesh=None) -> jnp.ndarray:
    """Drop-in attention fn: (B, H, S, D) x3 -> (B, H, S, D), sequence dim
    sharded over the mesh's ``sequence`` axis, batch over ``data``.

    Self-wraps in shard_map over the ambient mesh (``with mesh:``), so the
    ViT path can select it by name (``attn_impl='ring'``) without
    re-plumbing.  S must divide evenly by the sequence-axis size.
    """
    if mesh is None:
        mesh = ambient_mesh()
    if mesh is None or SEQUENCE_AXIS not in mesh.axis_names:
        raise ValueError(
            "ring_attention needs a mesh with a 'sequence' axis in scope "
            "(with mesh: ...) or passed explicitly")
    sp = mesh.shape[SEQUENCE_AXIS]
    if q.shape[2] % sp != 0:
        raise ValueError(
            f"sequence length {q.shape[2]} not divisible by sequence-"
            f"parallel size {sp}")
    spec = P(DATA_AXIS, None, SEQUENCE_AXIS, None)
    body = functools.partial(ring_attention_local, axis_name=SEQUENCE_AXIS)
    fn = ops_common.shard_map_unchecked(body, mesh, in_specs=(spec, spec, spec),
                                     out_specs=spec)
    return fn(q, k, v)
