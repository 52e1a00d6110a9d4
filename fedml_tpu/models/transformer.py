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

With ``arch`` (the published configuration keys of an expert model, by the
``model_type`` its file names) the same model is built from other parts:
RMSNorm before each half of a block and before the head, rotary positions,
gated SiLU MLPs without biases, an untied head, one `jax.checkpoint` a
block (`DecoderBlock`, `TransformerLM._decoder`), and the attention and the
expert layer the arch states:

* `LatentMoEArch` (DeepSeek-V3 / GLM-4.7 family): latent attention
  (`LatentAttention`: queries and keys/values through low-rank latents, one
  rotary key shared by all heads), ``first_k_dense_replace`` dense blocks
  and then expert blocks (`models.moe.HeldExpertMoE`: the share of the
  sigmoid-routed experts this chip holds, and the shared expert), and
  ``num_nextn_predict_layers`` multi-token prediction modules whose loss
  term is sown into ``losses``;
* `models.indexed_attention.IndexedGQAArch` (Keye-VL-2.0's language model):
  grouped-query attention with three-axis rotary positions whose keys a
  learned indexer selects, every block an expert block routed by softmax;
* `models.window_attention.WindowGQAArch` (Laguna-XS.2): grouped-query
  attention whose layers differ by kind (a 512-key window beside full
  causal attention), head count and rotary (YaRN over half of each head on
  the full layers), a dense block and then sigmoid-routed expert blocks
  with a shared expert.

Every core runs through `causal_blocked_attention`.  Training only: the
decode cache and the ring are the learned-position model's.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

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


# the `checkpoint_name` of an indexer's selection: a `DecoderBlock`'s
# checkpoint keeps it for the backward pass, beside what the fused core
# names (`models.fused_attention.SAVED`)
SELECTED = "attn_selected"

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


class ArchKeys:
    """What the dataclasses of published keys share: `from_dict`, and what
    `TransformerLM._decoder`, `DecoderBlock` and the workload ask of an
    arch beside its widths: ``position_rows`` (rows of positions the
    rotary takes: 1, or 3 for temporal / height / width), `attention` and
    `ffn` (the two halves of a block, as modules; `attention` is given the
    layer's index, which an arch whose layers differ by kind reads and
    the others pass over), and ``counters`` (name
    -> (collection, shape) of what its layers sow a step, which
    `trainer.workload.NWPWorkload` sums and `wave.dispatch` reports),
    and the two counts the scaffolding reads, ``first_k_dense_replace``
    and ``num_nextn_predict_layers`` (a field where the published file
    has the key, 0 where the family has neither)."""
    position_rows = 1

    @classmethod
    def from_dict(cls, d: dict):
        """From a configuration file's keys; a key this model has no use
        for (``model_type``, ``max_position_embeddings``) is passed over, a
        missing one is an error that names it."""
        names = {f.name: f for f in dataclasses.fields(cls)}
        missing = [n for n, f in names.items() if n not in d
                   and f.default is dataclasses.MISSING]
        if missing:
            raise KeyError(f"model configuration lacks {missing}")
        return cls(**{n: d[n] for n in names if n in d})


@dataclasses.dataclass(frozen=True)
class LatentMoEArch(ArchKeys):
    """The architecture's keys under their published names (a model's
    ``config.json``, ``model_type`` ``glm4_moe_lite``), plus the share of a
    layer this chip holds:
    ``experts_held`` routed experts from ``first_held`` on and the first
    ``vocab_held`` rows of the vocabulary.  ``initializer_range`` and
    ``mtp_loss_weight`` are not in the published file; their defaults are
    the family's.  ``embedding_range`` is the scale the token embedding
    starts from: 1 and not ``initializer_range``, because RMSNorm puts
    every block's input at scale 1 and its output near it, so rows of
    scale 0.02 are drowned by the first attention's mean value vector,
    every position looks alike to an untrained router, and all tokens
    take the same ``num_experts_per_tok`` experts (measured at the
    published widths: 41 of 2,048 choices held where 256 are expected,
    233 at scale 1; PERF.md section 6, PR 37)."""
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    rms_norm_eps: float
    rope_theta: float
    vocab_size: int
    num_nextn_predict_layers: int
    experts_held: int
    first_held: int
    vocab_held: int
    initializer_range: float = 0.02
    mtp_loss_weight: float = 0.3
    embedding_range: float = 1.0

    def attention(self, dtype, block_size, layer):
        return LatentAttention(self, dtype, block_size, name="attn")

    def ffn(self, experts: bool, dtype):
        from fedml_tpu.models.moe import GatedMLP, HeldExpertMoE
        if not experts:
            return GatedMLP(self.intermediate_size, self.initializer_range,
                            dtype, name="mlp")
        return HeldExpertMoE(
            self.n_routed_experts, self.experts_held, self.first_held,
            self.num_experts_per_tok, self.moe_intermediate_size,
            n_shared=self.n_shared_experts,
            scale=self.routed_scaling_factor, normalize=self.norm_topk_prob,
            init_std=self.initializer_range, dtype=dtype, name="moe")

    @property
    def counters(self) -> dict:
        # every attention its core and whether the fused kernels took
        # it, every expert layer its tokens
        experts = self.num_hidden_layers > self.first_k_dense_replace
        return {"attn": ("attn_stats", (2,)),
                **({"moe": ("moe_stats", (5,))} if experts else {})}


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: object = None

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (y * scale).astype(self.dtype or x.dtype)


def rotary(x, positions, theta: float, sections=None, freq=None,
           scale: float = 1.0):
    """Rotary position embedding over the last axis of ``x`` [B, T, H, r],
    pairing element ``i`` with ``i + r/2`` (the rotate-half convention).
    ``positions`` [T] turns every frequency.  With ``sections`` (numbers of
    frequencies that add up to ``r/2``) ``positions`` is [len(sections),
    T] and row ``a`` turns the ``a``-th run of frequencies (the chunked
    multi-axis layout of Qwen2-VL: temporal, height, width).  ``freq``
    [r/2], where given, replaces the frequencies ``theta^(-j / (r/2))``
    (`yarn_inv_freq`'s), and ``scale`` multiplies cos and sin (YaRN's
    attention factor)."""
    half = x.shape[-1] // 2
    if freq is None:
        freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if sections is None:
        at = positions[:, None]
    else:
        if sum(sections) != half:
            raise ValueError(f"rotary sections {tuple(sections)} do not add "
                             f"up to the {half} frequencies of a head")
        at = positions[np.repeat(np.arange(len(sections)), sections)].T
    angle = at.astype(jnp.float32) * freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's inverse frequencies (Peng et al., arXiv:2309.00071) of a
    rotary over ``dim`` elements, float32 [dim / 2], as transformers'
    ``_compute_yarn_parameters`` makes them with ``truncate`` on: each
    frequency ``theta^(-2j / dim)`` is kept (extrapolated) where it turns
    more than ``beta_fast`` times over ``original`` positions, divided by
    ``factor`` (interpolated) where it turns fewer than ``beta_slow``
    times, and ramped linearly between over the whole dimensions the two
    bounds fall in.  The attention factor is the caller's (`rotary`'s
    ``scale``)."""
    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = np.float32(theta) ** (
        np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim))
    extrapolated = np.float32(1.0) / pos_freqs
    interpolated = np.float32(1.0) / (np.float32(factor) * pos_freqs)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - np.float32(low))
                   / np.float32(high - low), 0, 1)
    kept = np.float32(1.0) - ramp
    return (interpolated * (1 - kept) + extrapolated * kept).astype(
        np.float32)


def fused_core_fits(q, k, v, selected=None, window=None) -> bool:
    """Whether `causal_blocked_attention` hands these to the fused kernels
    (`models.fused_attention`): on a TPU, with a selection, a window or
    neither, key heads that divide the query heads, and float32 with a
    sequence that is a whole number of the kernels' blocks, head widths
    that are multiples of 128 and a head that fits in VMEM."""
    from fedml_tpu.models import fused_attention
    return (jax.default_backend() == "tpu"
            and fused_attention.admits(q, k, v, selected, window))


def causal_blocked_attention(q, k, v, block: Optional[int] = None,
                             selected=None, window: Optional[int] = None):
    """Causal softmax attention, ``q`` [B, T, H, dk], ``k`` [B, T, Hkv, dk]
    and ``v`` [B, T, Hkv, dv] at positions 0..T-1; query head ``h`` reads
    key/value head ``h // (H / Hkv)``.  ``selected`` [B, T, T] bool, where
    given, says which keys each query sees (a subset of its causal past
    in which every row selects a key: `models.indexed_attention`); None is
    all of it.  ``window``, where given (a number of keys, at least 1;
    never with a selection), limits query ``t`` to the keys ``t - window <
    s <= t`` (`models.window_attention`): every row sees at least its own
    key.  One algorithm, and the inputs say which implementation of it
    runs (`fused_core_fits`):

    * on a TPU, for float32 inputs whose ``T`` is a whole number of 512,
      whose ``dk`` and ``dv`` are multiples of 128, whose ``Hkv`` divides
      ``H`` and whose head fits in VMEM (``T * max(dk, dv) <= 8192 *
      256``), with a selection, a window or neither: the fused Pallas
      kernels of `models.fused_attention`, one a pass, scores and
      probabilities in VMEM only, the log-sum-exp saved for the backward
      pass; without either and with ``Hkv == H`` its ``latent_attention``
      kernels, with a window its ``window_attention`` ones (the key tiles
      outside every row's window neither fetched nor computed), else its
      ``selected_attention`` ones (the selection a mask on every score
      tile, a key head read by its group of query heads through the
      index maps); ``block`` plays no part there;
    * anywhere else (the CPU, another dtype, a ragged or short ``T``, a
      narrow head, a head too long for VMEM): XLA, one block of
      ``block`` queries at a time against the keys up to its last
      position, from the first block of ``block`` keys that holds a key
      of its first row's window on: the scores held at once are [B, H,
      block, <= T], the selection or the window is a mask on them, and
      the blocks wholly above the diagonal or before the window are never
      computed (`window_tiles` counts them).  Each block is a
      `jax.checkpoint`, so the backward pass computes its scores again
      and keeps none.  ``block`` None is one block."""
    if window is not None and (selected is not None or window < 1):
        raise ValueError(f"a window of {window} keys: at least one key, "
                         f"and never beside a selection")
    if fused_core_fits(q, k, v, selected, window):
        from fedml_tpu.core.pallas_agg import pallas_interpret
        from fedml_tpu.models import fused_attention
        return fused_attention.fused_causal_attention(
            q, k, v, selected, window=window, interpret=pallas_interpret(
                fused_attention.kernel_name(q.shape[2] // k.shape[2],
                                            selected, window)))
    return _xla_blocked_attention(q, k, v, block, selected, window)


def window_tiles(t: int, block: int, window: int):
    """``(causal, visited)``: of the key tiles (``block`` queries against
    ``block`` keys) on or below one head's diagonal, the number whose keys
    some row of the query block may see under a ``window`` of keys, the
    tiles `causal_blocked_attention` computes with a window, whichever
    path takes it.  Counted from the bounds both paths' loops read
    (`fused_attention.window_key_blocks`): what those bounds give, not a
    count the kernels keep."""
    from fedml_tpu.models.fused_attention import window_key_blocks
    n = -(-t // block)
    return (n * (n + 1) // 2,
            sum(i + 1 - window_key_blocks(i, block, window)[0]
                for i in range(n)))


def _xla_blocked_attention(q, k, v, block: Optional[int] = None,
                           selected=None, window: Optional[int] = None):
    from fedml_tpu.models.fused_attention import window_key_blocks
    b, t, h, _ = q.shape
    kv = k.shape[2]
    g = h // kv
    block = t if block is None else min(block, t)
    scale = 1.0 / math.sqrt(q.shape[-1])

    # the g query heads that read one key/value head stand as g runs of
    # rows under that head: one product a key/value head, no copy of it
    # (nothing is moved where every query head has its own)
    def heads_as_rows(x):           # [B, n, H, d] -> [B, g * n, Hkv, d]
        n = x.shape[1]
        return x if g == 1 else x.reshape(b, n, kv, g, -1).transpose(
            0, 3, 1, 2, 4).reshape(b, g * n, kv, -1)

    def rows_as_heads(x):           # and back
        n = x.shape[1] // g
        return x if g == 1 else x.reshape(b, g, n, kv, -1).transpose(
            0, 2, 3, 1, 4).reshape(b, n, h, -1)

    @jax.checkpoint
    def one(qb, kb, vb, q_pos, chosen):
        # ``q_pos``: the rows' positions counted from the first key given
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb,
                       preferred_element_type=jnp.float32) * scale
        if chosen is not None:
            seen = chosen
        else:
            k_pos = jnp.arange(kb.shape[1])[None, :]
            seen = k_pos <= q_pos[:, None]
            if window is not None:
                seen = seen & (k_pos > q_pos[:, None] - window)
        p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(vb.dtype), vb,
                          preferred_element_type=jnp.float32)

    out = []
    for lo in range(0, t, block):
        hi = min(lo + block, t)
        # the first block of keys that holds a key of row lo's window
        first = 0 if window is None else \
            window_key_blocks(lo // block, block, window)[0] * block
        # a selection lies inside the causal past: it is the whole mask,
        # each query's for every head of its group
        chosen = None if selected is None else jnp.tile(
            selected[:, None, lo:hi, :hi], (1, 1, g, 1))
        q_pos = jnp.arange(lo - first, hi - first)
        out.append(rows_as_heads(one(
            heads_as_rows(q[:, lo:hi]), k[:, first:hi], v[:, first:hi],
            q_pos if g == 1 else jnp.tile(q_pos, g), chosen)))
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 section
    2.1): ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` (heads x (nope +
    rope)); ``[c_kv, k_r] = x W_kva``, ``c_kv = RMSNorm(c_kv)``,
    ``[k_nope, v] = c_kv W_kvb``; rotary on ``q``'s rope part and on
    ``k_r``, which every head shares; causal softmax of ``q k^T /
    sqrt(nope + rope)``; heads x ``v_head_dim`` through ``W_o``.  Training
    decompresses keys and values and runs `causal_blocked_attention`.
    Sows ``attn_stats/calls``, float32 ``[1, fused]``."""
    arch: LatentMoEArch
    dtype: object = None
    block_size: Optional[int] = None

    @nn.compact
    def __call__(self, x, positions):
        a = self.arch
        b, t, _ = x.shape
        h, nope, rope = (a.num_attention_heads, a.qk_nope_head_dim,
                         a.qk_rope_head_dim)

        def dense(n, name):
            return nn.Dense(n, use_bias=False, dtype=self.dtype, name=name,
                            kernel_init=nn.initializers.normal(
                                a.initializer_range))
        c_q = RMSNorm(a.rms_norm_eps, self.dtype, name="q_norm")(
            dense(a.q_lora_rank, "q_a")(x))
        q = dense(h * (nope + rope), "q_b")(c_q).reshape(b, t, h, nope + rope)
        ckv = dense(a.kv_lora_rank + rope, "kv_a")(x)
        c_kv = RMSNorm(a.rms_norm_eps, self.dtype, name="kv_norm")(
            ckv[..., :a.kv_lora_rank])
        k_r = rotary(ckv[..., None, a.kv_lora_rank:], positions, a.rope_theta)
        kv = dense(h * (nope + a.v_head_dim), "kv_b")(c_kv).reshape(
            b, t, h, nope + a.v_head_dim)
        q = jnp.concatenate(
            [q[..., :nope], rotary(q[..., nope:], positions, a.rope_theta)],
            axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_r, (b, t, h, rope))], axis=-1)
        v = kv[..., nope:]
        # one attention core handed over, and whether the kernels took it
        # (`wave.dispatch`'s ``attn_calls`` / ``attn_calls_fused``)
        self.sow("attn_stats", "calls", jnp.array(
            [1.0, float(fused_core_fits(q, k, v))], jnp.float32))
        out = causal_blocked_attention(q, k, v, self.block_size)
        return dense(a.hidden_size, "o")(
            out.astype(x.dtype).reshape(b, t, h * a.v_head_dim))


class DecoderBlock(nn.Module):
    """``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; the
    attention the arch's for layer ``layer`` (a field: the block's
    checkpoint sees no traced argument for it), the FFN its dense gated
    MLP or its expert layer."""
    arch: ArchKeys
    experts: bool
    dtype: object = None
    block_size: Optional[int] = None
    layer: int = 0

    @nn.compact
    def __call__(self, x, positions):
        a = self.arch
        h = x + a.attention(self.dtype, self.block_size, self.layer)(
            RMSNorm(a.rms_norm_eps, self.dtype, name="attn_norm")(x),
            positions)
        return h + a.ffn(self.experts, self.dtype)(
            RMSNorm(a.rms_norm_eps, self.dtype, name="ffn_norm")(h))


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
    arch: Optional[ArchKeys] = None  # the published keys of an expert
    #                       model (`LatentMoEArch`, `IndexedGQAArch`,
    #                       `WindowGQAArch`): every
    #                       width then comes from it and the fields above
    #                       that state one are not read

    @nn.compact
    def __call__(self, input_seq, train: bool = False, positions=None,
                 ring_axis: Optional[str] = None,
                 cache: Optional[dict] = None):
        decode = cache is not None
        if self.arch is not None:
            if decode or ring_axis is not None:
                raise NotImplementedError(
                    "a --model_config model trains only: neither its "
                    "decode cache (latent keys, an indexer's keys "
                    "beside the selected ones, or a window layer's "
                    "cache beside a full one's) nor the ring is built")
            return self._decoder(input_seq, positions)
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

    def _decoder(self, tokens, positions):
        """The forward pass under ``arch`` (called inside `__call__`)."""
        a = self.arch
        t = tokens.shape[1]
        if positions is None:
            # text: every axis of the rotary at the token's index
            positions = jnp.arange(t) if a.position_rows == 1 else \
                jnp.broadcast_to(jnp.arange(t), (a.position_rows, t))
        init = nn.initializers.normal(a.initializer_range)
        embed = nn.Embed(a.vocab_held, a.hidden_size, dtype=self.dtype,
                         embedding_init=nn.initializers.normal(
                             a.embedding_range), name="tok_embed")
        final_norm = RMSNorm(a.rms_norm_eps, self.dtype, name="final_norm")
        head = nn.Dense(a.vocab_held, use_bias=False, dtype=self.dtype,
                        kernel_init=init, name="lm_head")
        # a block is computed again for its backward pass, but for what
        # its attention names: the fused core's result and log-sum-exp,
        # so its forward kernel runs once a step, and an indexer's
        # selection, so it is made once a step (the XLA core names
        # nothing, and nothing of it is kept)
        from fedml_tpu.models.fused_attention import SAVED
        block = nn.remat(
            DecoderBlock,
            policy=jax.checkpoint_policies.save_only_these_names(
                *SAVED, SELECTED))

        x = embed(tokens)
        for i in range(a.num_hidden_layers):
            x = block(a, i >= a.first_k_dense_replace, self.dtype,
                      self.block_size, i, name=f"layer_{i}")(x, positions)
        logits = head(final_norm(x))
        if a.num_nextn_predict_layers > 1:
            raise NotImplementedError("one multi-token prediction module")
        if a.num_nextn_predict_layers and t >= 3:
            # DeepSeek-V3 (arXiv:2412.19437, section 2.2): position i's
            # hidden state and the embedding of token i+1 go through one
            # more block, the shared norm and head, to predict token i+2;
            # the mean over real targets, weighted, is sown
            merged = jnp.concatenate(
                [RMSNorm(a.rms_norm_eps, self.dtype,
                         name="mtp_hnorm")(x[:, :-1]),
                 RMSNorm(a.rms_norm_eps, self.dtype,
                         name="mtp_enorm")(embed(tokens[:, 1:]))], axis=-1)
            y = block(a, True, self.dtype, self.block_size,
                      a.num_hidden_layers, name="mtp_block")(
                nn.Dense(a.hidden_size, use_bias=False, dtype=self.dtype,
                         kernel_init=init, name="mtp_proj")(merged),
                positions[:-1])
            mtp_logits = head(final_norm(y))[:, :-1].astype(jnp.float32)
            target = tokens[:, 2:]
            real = (target != self.pad_id).astype(jnp.float32)
            nll = -jnp.take_along_axis(
                jax.nn.log_softmax(mtp_logits, axis=-1), target[..., None],
                axis=-1)[..., 0]
            self.sow("losses", "mtp", a.mtp_loss_weight
                     * jnp.sum(nll * real) / jnp.maximum(jnp.sum(real), 1.0))
        return logits
