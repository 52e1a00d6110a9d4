"""Decoder-only transformer LM — the attention member of the NLP family.

The reference's NLP zoo stops at LSTMs (fedml_api/model/nlp/rnn.py:4-70);
this model is the modern drop-in for the same next-word/char-prediction
workloads ([B, T] tokens in, [B, T, V] per-position logits out — the
NWPWorkload contract), and the carrier for the framework's long-context
story: pass ``ring_axis`` (inside a shard_map over a ``sequence`` mesh axis,
see fedml_tpu.parallel.ring_attention) and the same parameters run with the
sequence sharded across devices and exact ring attention over ICI.

Architecture: pre-LN blocks (LN → causal MHA → residual, LN → GELU MLP →
residual), learned positional embeddings, final LN → vocab head.  ``dtype``
enables bf16 mixed precision the same way as the rest of the zoo (params
stay f32; softmax/logits accumulate f32).

Incremental decode (the serving hot path, ISSUE 15): pass ``cache`` (built
by `init_decode_cache`) and per-slot ``positions`` to run ONE token per
slot against per-layer KV caches carried as explicit state — the model
returns ``(logits [B, V], new_cache)`` instead of re-running the whole
prefix every token.  The cache is plain pytree state (no flax mutable
collections), so the serving scheduler jits one step over a fixed
``[slots]`` batch and donates the cache in place; per-slot positions mean
every slot may sit at a DIFFERENT sequence index, which is exactly what
continuous batching needs (a finished slot restarts at position 0 and the
``kv_idx <= position`` mask hides the previous occupant's stale rows).
"""

from __future__ import annotations

import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from fedml_tpu.parallel.ring_attention import (
    blockwise_attention, full_attention, ring_attention)


def _auto_block(t: int, threshold: int, max_block: int = 512,
                min_block: int = 64) -> Optional[int]:
    """Largest kv-block size in [min_block, max_block] dividing ``t``, or
    None when ``t <= threshold`` (dense is fine) or no usable divisor
    exists (a sub-64 block would make the scan slower than it saves —
    realistic sequence lengths have power-of-two factors)."""
    if t <= threshold:
        return None
    for b in range(min(max_block, t), min_block - 1, -1):
        if t % b == 0:
            return b
    return None


# the flash kernel's q/kv block: the sequence must be a whole number of
# these (its default BlockSizes; `create_workload` checks at config time)
FLASH_BLOCK = 128


def _pallas_flash(q, k, v):
    """TPU-fused flash attention (jax.experimental.pallas.ops.tpu) for the
    dense causal case — one VMEM-tiled kernel instead of XLA-scheduled
    matmul+softmax.  TPU backend only; q/k/v are [B, T, H, d] with T a
    multiple of `FLASH_BLOCK`."""
    if jax.default_backend() != "tpu" or q.shape[1] % FLASH_BLOCK:
        raise RuntimeError(
            f"use_flash=True needs a TPU backend and a sequence that is a "
            f"multiple of {FLASH_BLOCK} (got {jax.default_backend()!r}, "
            f"T={q.shape[1]}); use block_size= for a backend-neutral "
            f"memory-efficient path")
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention)
    # kernel layout is [B, H, T, d]
    out = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), causal=True,
                          sm_scale=1.0 / (q.shape[-1] ** 0.5))
    return out.transpose(0, 2, 1, 3)


class CausalSelfAttention(nn.Module):
    n_heads: int
    d_model: int
    dtype: object = None
    block_size: Optional[int] = None  # flash-style kv blocking (single-chip
    #                                   long context); None = dense scores
    use_flash: bool = False  # TPU pallas flash kernel (dense causal only)
    # dense attention materializes [B, H, T, T] scores; past this length
    # switch to blockwise automatically (exact same math) so long-context
    # eval/init can't OOM just because no backend flag was passed
    auto_block_len: int = 1024

    @nn.compact
    def __call__(self, x, positions, ring_axis: Optional[str] = None,
                 cache: Optional[dict] = None):
        d_head = self.d_model // self.n_heads
        q = nn.DenseGeneral((self.n_heads, d_head), dtype=self.dtype,
                            name="query")(x)
        k = nn.DenseGeneral((self.n_heads, d_head), dtype=self.dtype,
                            name="key")(x)
        v = nn.DenseGeneral((self.n_heads, d_head), dtype=self.dtype,
                            name="value")(x)
        t = x.shape[1]
        new_cache = None
        if cache is not None:
            # incremental decode: x is [B, 1, D], positions is [B] — the
            # per-slot write index.  Scatter this token's k/v into the
            # cache row, attend the single query against the whole cache
            # with a per-slot causal mask (kv_idx <= position): rows past
            # the slot's own position — including a previous occupant's
            # stale entries after slot reuse — are masked out, so a slot
            # restarting at position 0 is bit-equivalent to a fresh cache.
            k_cache, v_cache = cache["k"], cache["v"]   # [B, Tc, H, d]
            tc = k_cache.shape[1]
            write = (jnp.arange(tc)[None, :]
                     == positions[:, None])[:, :, None, None]
            k_cache = jnp.where(write, k.astype(k_cache.dtype), k_cache)
            v_cache = jnp.where(write, v.astype(v_cache.dtype), v_cache)
            new_cache = {"k": k_cache, "v": v_cache}
            scale = 1.0 / math.sqrt(d_head)
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk", q, k_cache,
                preferred_element_type=jnp.float32) * scale
            mask = (jnp.arange(tc)[None, None, None, :]
                    <= positions[:, None, None, None])
            scores = jnp.where(mask, scores, -1e30)
            p = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum("bhqk,bkhd->bqhd", p,
                             v_cache.astype(jnp.float32))
        elif ring_axis is not None:
            out = ring_attention(q, k, v, positions, positions, ring_axis)
        elif self.use_flash:
            out = _pallas_flash(q, k, v)
        elif self.block_size is not None:
            out = blockwise_attention(q, k, v, positions, positions,
                                      self.block_size)
        elif (blk := _auto_block(t, self.auto_block_len)) is not None:
            out = blockwise_attention(q, k, v, positions, positions, blk)
        else:
            out = full_attention(q, k, v, positions, positions)
        out = out.astype(x.dtype)
        out = nn.DenseGeneral(self.d_model, axis=(-2, -1),
                              dtype=self.dtype, name="out")(out)
        return (out, new_cache) if cache is not None else out


def init_decode_cache(model: "TransformerLM", slots: int, cache_len: int,
                      dtype=jnp.float32) -> dict:
    """Fresh per-layer KV cache for incremental decode: one
    ``{"attn_i": {"k", "v"}}`` entry per layer, each ``[slots, cache_len,
    n_heads, d_head]``.  Zeros are fine as the initial value — the
    per-slot ``kv_idx <= position`` mask in `CausalSelfAttention` never
    reads a row the slot's own steps have not written."""
    if cache_len > model.max_len:
        raise ValueError(
            f"cache_len {cache_len} exceeds the model's max_len "
            f"{model.max_len}: the positional embedding table has no row "
            f"for those positions; shrink the cache or grow max_len")
    d_head = model.d_model // model.n_heads
    shape = (slots, cache_len, model.n_heads, d_head)
    return {f"attn_{i}": {"k": jnp.zeros(shape, dtype),
                          "v": jnp.zeros(shape, dtype)}
            for i in range(model.n_layers)}


class TransformerLM(nn.Module):
    """Per-position next-token logits, causal.

    ``positions`` are global token indices (default ``arange(T)``); under
    sequence parallelism each shard passes its own offset block so the
    positional embedding and causal mask stay globally correct.

    Incremental decode: with ``cache`` (from `init_decode_cache`),
    ``input_seq`` is ONE token per slot (``[B]`` ints), ``positions`` the
    per-slot sequence index (``[B]`` ints), and the call returns
    ``(logits [B, vocab], new_cache)`` — the prediction for position
    ``positions + 1`` given everything the cache holds up to and
    including this token."""
    vocab_size: int
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 512
    max_len: int = 2048
    dropout_rate: float = 0.0
    dtype: object = None
    block_size: Optional[int] = None  # see CausalSelfAttention
    use_flash: bool = False           # see CausalSelfAttention
    auto_block_len: int = 1024        # see CausalSelfAttention
    moe_experts: int = 0        # >0: Switch MoE FFN with this many experts
    #                             (models/moe.py) — the ep-shardable form;
    #                             NWPWorkload adds the sown balance loss
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01      # Switch paper's alpha
    pad_id: int = 0       # pad token id; MoE routing excludes pad positions
    #                       (they would otherwise eat expert capacity)

    @nn.compact
    def __call__(self, input_seq, train: bool = False, positions=None,
                 ring_axis: Optional[str] = None,
                 cache: Optional[dict] = None):
        decode = cache is not None
        if decode:
            if positions is None:
                raise ValueError(
                    "decode (cache=) needs per-slot positions: each slot "
                    "sits at its own sequence index")
            if ring_axis is not None:
                raise ValueError(
                    "decode (cache=) is single-chip attention over the kv "
                    "cache; ring_axis does not compose with it")
            tokens = input_seq.reshape(-1)          # [B] one token/slot
            seq_for_mask = tokens[:, None]          # [B, 1] (MoE pad mask)
            x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                         name="tok_embed")(tokens)[:, None, :]
            x = x + nn.Embed(self.max_len, self.d_model, dtype=self.dtype,
                             name="pos_embed")(positions)[:, None, :]
        else:
            _, t = input_seq.shape
            if positions is None:
                positions = jnp.arange(t)
            seq_for_mask = input_seq
            x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                         name="tok_embed")(input_seq)
            x = x + nn.Embed(self.max_len, self.d_model, dtype=self.dtype,
                             name="pos_embed")(positions)[None, :, :]
        new_cache = {} if decode else None
        for i in range(self.n_layers):
            h = nn.LayerNorm(dtype=self.dtype)(x)
            attn = CausalSelfAttention(self.n_heads, self.d_model,
                                       dtype=self.dtype,
                                       block_size=self.block_size,
                                       use_flash=self.use_flash,
                                       auto_block_len=self.auto_block_len,
                                       name=f"attn_{i}")
            if decode:
                h, new_cache[f"attn_{i}"] = attn(
                    h, positions, cache=cache[f"attn_{i}"])
            else:
                h = attn(h, positions, ring_axis)
            if self.dropout_rate:
                h = nn.Dropout(self.dropout_rate,
                               deterministic=decode or not train)(h)
            x = x + h
            h = nn.LayerNorm(dtype=self.dtype)(x)
            if self.moe_experts:
                from fedml_tpu.models.moe import SwitchFFN
                h = SwitchFFN(self.moe_experts, self.d_model, self.d_ff,
                              capacity_factor=self.moe_capacity_factor,
                              dtype=self.dtype, name=f"moe_{i}")(
                    h, mask=(seq_for_mask != self.pad_id))
            else:
                h = nn.Dense(self.d_ff, dtype=self.dtype)(h)
                h = nn.gelu(h)
                h = nn.Dense(self.d_model, dtype=self.dtype)(h)
            if self.dropout_rate:
                h = nn.Dropout(self.dropout_rate,
                               deterministic=decode or not train)(h)
            x = x + h
        x = nn.LayerNorm(dtype=self.dtype)(x)
        logits = nn.Dense(self.vocab_size, dtype=self.dtype,
                          name="lm_head")(x)
        return (logits[:, 0, :], new_cache) if decode else logits
