"""Grouped-query attention whose keys a learned indexer selects
(DeepSeek sparse attention; ISSUE 39): the attention of Keye-VL-2.0's
language model (``model_type`` ``KeyeVL2``) under
`models.transformer.TransformerLM`'s ``arch`` scaffolding.

With ``u`` the block's normed input [T, hidden]:

* heads: ``q = u W_q`` [T, heads, head_dim], ``k``, ``v`` [T, kv heads,
  head_dim], ``q`` and ``k`` each through an RMSNorm over the head width,
  three-axis rotary (`models.transformer.rotary` with ``mrope_section``);
* indexer (DeepSeek-V3.2-Exp's lightning indexer): ``qI = rot(u W_qI)``
  [T, 16, 64], ``kI = rot(LayerNorm(u W_kI))`` [T, 64] (one key head), ``w =
  (u W_w) * 16^-1/2 * 64^-1/2``; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
  kI[s])`` for ``s <= t``;
* selection: the ``topk`` keys ``s <= t`` of largest ``I[t, .]`` (all of
  them while ``t < topk``), ties to the smaller ``s`` as `jax.lax.top_k`
  breaks them (`topk_mask`).  Integers: no gradient passes through it,
  and the indexer's leaves receive none;
* core: `causal_blocked_attention` with the selection as an operand and
  the key heads grouped.

``I`` is held a block of queries at a time (`index_selection`); the
selection [B, T, T] bool carries a `checkpoint_name`, which the block's
checkpoint keeps, so it is made once a step and not again for the backward
pass.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from fedml_tpu.models.transformer import (
    SELECTED, ArchKeys, RMSNorm, causal_blocked_attention, fused_core_fits,
    rotary)


@dataclasses.dataclass(frozen=True)
class IndexedGQAArch(ArchKeys):
    """Keye-VL-2.0's language model's keys (``model_type`` ``KeyeVL2``)
    under their published names, the nested groups flattened
    (``rope_scaling.mrope_section``, ``sa_config``'s indexer sizes and
    ``topk`` as ``index_topk``), plus the share of a layer this chip
    holds: ``experts_held`` routed experts from ``first_held`` on and the
    first ``vocab_held`` rows of the vocabulary.  Every layer is an expert
    layer (``mlp_only_layers`` [], ``decoder_sparse_step`` 1), routed by
    softmax without a shared expert.  ``initializer_range`` and
    ``embedding_range`` as `LatentMoEArch`'s and for its reason."""
    hidden_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    num_experts: int
    num_experts_per_tok: int
    norm_topk_prob: bool
    rms_norm_eps: float
    rope_theta: float
    vocab_size: int
    mrope_section: Tuple[int, ...]
    indexer_num_heads: int
    indexer_head_dim: int
    index_topk: int
    experts_held: int
    first_held: int
    vocab_held: int
    initializer_range: float = 0.02
    embedding_range: float = 1.0

    position_rows = 3               # temporal, height, width
    first_k_dense_replace = 0       # every layer an expert layer
    num_nextn_predict_layers = 0    # no multi-token prediction module

    @classmethod
    def from_dict(cls, d: dict) -> "IndexedGQAArch":
        sa = d.get("sa_config") or {}
        unbuilt = {"mlp_only_layers": [], "decoder_sparse_step": 1,
                   "use_sliding_window": False}
        for key, only in unbuilt.items():
            if d.get(key, only) != only:
                raise NotImplementedError(
                    f"{key} = {d[key]!r}: only {only!r} is built")
        if sa.get("indexer_num_kv_heads", 1) != 1:
            raise NotImplementedError("an indexer of one key head is built")
        flat = {**d, **{k: sa[k] for k in ("indexer_num_heads",
                                           "indexer_head_dim") if k in sa}}
        if "topk" in sa:
            flat["index_topk"] = sa["topk"]
        sections = (d.get("rope_scaling") or {}).get("mrope_section")
        if sections is not None:
            flat["mrope_section"] = tuple(sections)
        return super().from_dict(flat)

    def attention(self, dtype, block_size, layer):
        return IndexedAttention(self, dtype, block_size, name="attn")

    def ffn(self, experts: bool, dtype):
        from fedml_tpu.models.moe import HeldExpertMoE
        return HeldExpertMoE(
            self.num_experts, self.experts_held, self.first_held,
            self.num_experts_per_tok, self.moe_intermediate_size,
            n_shared=0, normalize=self.norm_topk_prob,
            init_std=self.initializer_range, dtype=dtype, router="softmax",
            name="moe")

    @property
    def counters(self) -> dict:
        return {"attn": ("attn_stats", (2,)),
                "select": ("select_stats", (2,)),
                "moe": ("moe_stats", (5,))}


def topk_mask(scores, k: int, valid):
    """``[..., Q, K]`` bool: per row the ``k`` entries of largest
    ``scores`` (float32) among those ``valid`` says may be chosen, all of
    them where fewer than ``k`` may; equal scores go to the smaller
    index, as `jax.lax.top_k` breaks them.

    No sort: float32 is mapped onto uint32 in its own order, the ``k``-th
    largest key of a row is found a bit at a time (32 counts of the
    entries at or above a candidate), and the entries above it are taken
    with as many of those equal to it, from the left, as are still
    wanted."""
    u = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.uint32)
    # sign set: all bits flipped; else the sign bit set: ascending in the
    # float's order, and no real value (nor an infinity) maps to 0
    key = jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))
    key = jnp.where(valid, key, jnp.uint32(0))

    def refine(i, kth):
        bit = jnp.uint32(1) << (31 - i).astype(jnp.uint32)
        enough = jnp.sum(key >= (kth | bit)[..., None], axis=-1,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, kth | bit, kth)

    kth = jax.lax.fori_loop(0, 32, refine,
                            jnp.zeros(key.shape[:-1], jnp.uint32))
    above = key > kth[..., None]
    equal = (key == kth[..., None]) & valid
    wanted = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    return above | (equal & (jnp.cumsum(equal, axis=-1, dtype=jnp.int32)
                             <= wanted[..., None]))


def index_selection(q_i, k_i, w_i, topk: int, block: Optional[int] = None):
    """``[B, T, T]`` bool: the keys each query selects.  ``q_i`` [B, T,
    heads, d] and ``k_i`` [B, T, d] are the indexer's rotated queries and
    its one key head, ``w_i`` [B, T, heads] the scaled head weights; ``I[t,
    s] = sum_j w_i[t, j] relu(q_i[t, j] . k_i[s])`` over ``s <= t`` is held
    a block of ``block`` queries at a time against the keys up to the
    block's last position, and a block whose last position is under
    ``topk`` selects its whole causal past without scoring it."""
    b, t = q_i.shape[:2]
    block = t if block is None else min(block, t)
    rows = []
    for lo in range(0, t, block):
        hi = min(lo + block, t)
        causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        if hi <= topk:
            chosen = jnp.broadcast_to(causal, (b,) + causal.shape)
        else:
            s = jnp.einsum("bqhd,bkd->bhqk", q_i[:, lo:hi], k_i[:, :hi],
                           preferred_element_type=jnp.float32)
            w = w_i[:, lo:hi].astype(jnp.float32).transpose(0, 2, 1)
            index = jnp.sum(jax.nn.relu(s) * w[..., None], axis=1)
            chosen = topk_mask(index, topk, causal)
        rows.append(jnp.pad(chosen, ((0, 0), (0, 0), (0, t - hi))))
    return rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=1)


class IndexedAttention(nn.Module):
    """The attention of the module's docstring.  ``positions`` [3, T]
    (temporal, height, width); the indexer turns by the temporal row.
    Sows ``attn_stats/calls``, float32 ``[1, fused]`` as `LatentAttention`
    does, and ``select_stats/pairs``, float32 ``[causal (query, key) pairs,
    pairs selected]`` of this call."""
    arch: IndexedGQAArch
    dtype: object = None
    block_size: Optional[int] = None

    @nn.compact
    def __call__(self, x, positions):
        a = self.arch
        b, t, _ = x.shape
        h, kv, d = a.num_attention_heads, a.num_key_value_heads, a.head_dim
        ih, idim = a.indexer_num_heads, a.indexer_head_dim

        def dense(n, name):
            return nn.Dense(n, use_bias=False, dtype=self.dtype, name=name,
                            kernel_init=nn.initializers.normal(
                                a.initializer_range))

        def turned(y):
            return rotary(y, positions, a.rope_theta, a.mrope_section)
        q = turned(RMSNorm(a.rms_norm_eps, self.dtype, name="q_norm")(
            dense(h * d, "q")(x).reshape(b, t, h, d)))
        k = turned(RMSNorm(a.rms_norm_eps, self.dtype, name="k_norm")(
            dense(kv * d, "k")(x).reshape(b, t, kv, d)))
        v = dense(kv * d, "v")(x).reshape(b, t, kv, d)
        # the selection is integers: nothing flows back into the indexer
        u = jax.lax.stop_gradient(x)
        q_i = rotary(dense(ih * idim, "idx_q")(u).reshape(b, t, ih, idim),
                     positions[0], a.rope_theta)
        k_i = rotary(nn.LayerNorm(epsilon=1e-6, dtype=self.dtype,
                                  use_fast_variance=False,
                                  name="idx_k_norm")(
            dense(idim, "idx_k")(u))[:, :, None], positions[0],
            a.rope_theta)[:, :, 0]
        w_i = dense(ih, "idx_w")(u) * (ih ** -0.5 * idim ** -0.5)
        selected = checkpoint_name(
            index_selection(q_i, k_i, w_i, a.index_topk, self.block_size),
            SELECTED)
        self.sow("attn_stats", "calls", jnp.array(
            [1.0, float(fused_core_fits(q, k, v, selected))], jnp.float32))
        self.sow("select_stats", "pairs", jnp.stack([
            jnp.float32(b * (t * (t + 1) // 2)),
            jnp.sum(selected, dtype=jnp.float32)]))
        out = causal_blocked_attention(q, k, v, self.block_size, selected)
        return dense(a.hidden_size, "o")(
            out.astype(x.dtype).reshape(b, t, h * d))
