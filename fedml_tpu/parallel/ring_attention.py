"""Ring attention: sequence/context parallelism over a device mesh.

The reference has no long-context machinery at all (SURVEY.md §5.7 — its
largest NLP model is a 2-layer LSTM, fedml_api/model/nlp/rnn.py:18-22), so
sequences are capped by one device's memory.  This module removes that cap
the TPU way: the sequence axis is sharded across a ``sequence`` mesh axis,
each device holds a block of queries, and key/value blocks rotate around the
ring via `lax.ppermute` (one ICI hop per step) while a flash-attention-style
online softmax accumulates exact results — attention over a sequence of
length T costs each device O(T/D) memory instead of O(T), with compute and
communication overlapped by XLA across ring steps.

Exactness: the online-softmax recurrence (running max m, normalizer l,
unnormalized accumulator o) reproduces full softmax attention bitwise up to
float reassociation; `tests/test_ring_attention.py` checks parity against
the dense path on an 8-device mesh.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _online_softmax_block(q, k, v, q_pos, kv_pos, m, l, o, causal):
    """Accumulate one key/value block into the (m, l, o) running state.

    q [B, Tq, H, d]; k/v [B, Tk, H, d]; positions are GLOBAL token indices,
    so causal masking stays correct no matter which ring step delivered the
    block.  Scores and accumulators are f32 (softmax is range-sensitive);
    q/k/v may be bf16.
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = kv_pos[None, None, None, :] <= q_pos[None, None, :, None]
        scores = jnp.where(mask, scores, -1e30)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    p = jnp.exp(scores - m_new[..., None])
    if causal:
        # a fully-masked block has scores == m_new == -1e30, where the exp
        # above degenerates to 1 — zero those entries explicitly
        p = p * mask
    corr = jnp.exp(m - m_new)
    l = l * corr + p.sum(axis=-1)
    o = o * corr[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
    return m_new, l, o


def ring_attention(q, k, v, q_pos, kv_pos, axis_name: str,
                   causal: bool = True) -> jax.Array:
    """Exact attention with the sequence axis sharded over ``axis_name``.

    Must run inside `shard_map`.  Each device holds its local query block
    ``q [B, Tq_local, H, d]`` and initial key/value block; over D ring steps
    the k/v blocks (and their global position vector) rotate one neighbor
    forward via `ppermute`, and every device folds each visiting block into
    its online-softmax state.  Returns [B, Tq_local, H, d].

    The causal variant still visits every block (a fully-future block
    contributes zeros) — with D devices that wastes ~half the FLOPs vs a
    skew-scheduled ring, but keeps one program for causal and full attention;
    at FL model sizes attention is not the dominant cost.
    """
    n = jax.lax.psum(1, axis_name)
    B, Tq, H, d = q.shape
    m = jnp.full((B, H, Tq), -1e30, jnp.float32)
    l = jnp.zeros((B, H, Tq), jnp.float32)
    o = jnp.zeros((B, H, Tq, d), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]
    for s in range(n):
        m, l, o = _online_softmax_block(q, k, v, q_pos, kv_pos, m, l, o,
                                        causal)
        if s != n - 1:
            k, v, kv_pos = jax.lax.ppermute((k, v, kv_pos), axis_name, perm)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3)


def full_attention(q, k, v, q_pos, kv_pos, causal: bool = True) -> jax.Array:
    """Single-device dense path: the same online-softmax math with one block
    covering the whole sequence, so the sharded and dense paths can never
    drift numerically."""
    B, Tq, H, d = q.shape
    m = jnp.full((B, H, Tq), -1e30, jnp.float32)
    l = jnp.zeros((B, H, Tq), jnp.float32)
    o = jnp.zeros((B, H, Tq, d), jnp.float32)
    m, l, o = _online_softmax_block(q, k, v, q_pos, kv_pos, m, l, o, causal)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3)


def blockwise_attention(q, k, v, q_pos, kv_pos, block_size: int,
                        causal: bool = True) -> jax.Array:
    """Single-device flash-style attention: `lax.scan` over key/value blocks
    with the same online-softmax state as the ring — O(T·block) peak memory
    for the scores instead of the dense path's O(T²), so one chip can run
    sequences far past the [B, H, T, T] materialization limit.  Exact (same
    accumulation as `full_attention`); the backward pass rematerializes each
    block's scores through the scan's VJP.

    ``block_size`` must divide the key length.
    """
    B, Tk, H, d = k.shape
    if Tk % block_size:
        raise ValueError(f"block_size {block_size} must divide key length "
                         f"{Tk}")
    Tq = q.shape[1]
    n_blocks = Tk // block_size
    k_b = k.reshape(B, n_blocks, block_size, H, d).transpose(1, 0, 2, 3, 4)
    v_b = v.reshape(B, n_blocks, block_size, H, d).transpose(1, 0, 2, 3, 4)
    pos_b = kv_pos.reshape(n_blocks, block_size)

    m0 = jnp.full((B, H, Tq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, H, Tq), jnp.float32)
    o0 = jnp.zeros((B, H, Tq, d), jnp.float32)

    def scan_body(carry, blk):
        m, l, o = carry
        kb, vb, pb = blk
        m, l, o = _online_softmax_block(q, kb, vb, q_pos, pb, m, l, o,
                                        causal)
        return (m, l, o), None

    (m, l, o), _ = jax.lax.scan(scan_body, (m0, l0, o0), (k_b, v_b, pos_b))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3)


def make_sequence_parallel_apply(model, mesh: Mesh,
                                 axis_name: str = "sequence"):
    """Jit ``model.apply`` with activations sharded on the sequence axis.

    ``model`` is a TransformerLM (or any module taking ``positions`` and
    ``ring_axis``).  Params replicate; the [B, T] token array shards its T
    axis over ``axis_name``; each device computes its block's global
    positions from its mesh coordinate, and attention runs as a ring.
    Output logits come back sharded the same way ([B, T, V] on T).
    """

    def _apply(params, x):
        t_local = x.shape[1]
        idx = jax.lax.axis_index(axis_name)
        positions = idx * t_local + jnp.arange(t_local)
        return model.apply({"params": params}, x, positions=positions,
                           ring_axis=axis_name)

    fn = jax.shard_map(
        _apply, mesh=mesh,
        in_specs=(P(), P(None, axis_name)),
        out_specs=P(None, axis_name))
    return jax.jit(fn)


def make_sequence_mesh(n_devices: Optional[int] = None,
                       axis_name: str = "sequence") -> Mesh:
    import numpy as np
    devs = jax.devices()
    n = n_devices or len(devs)
    return Mesh(np.asarray(devs[:n]), (axis_name,))
