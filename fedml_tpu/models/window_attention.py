"""Grouped-query attention whose layers differ by kind: a window of the
latest keys beside full causal attention, each kind with its own number of
query heads and its own rotary (Laguna-XS.2, ``model_type`` ``laguna``),
under `models.transformer.TransformerLM`'s ``arch`` scaffolding.

With ``u`` the block's normed input [T, hidden] and ``H`` the layer's
query heads (``num_attention_heads_per_layer``):

* heads: ``q = u W_q`` [T, H, head_dim], ``k``, ``v`` [T, kv heads,
  head_dim], no biases and no norms on q or k;
* rotary by ``layer_types``: a ``full_attention`` layer turns the first
  ``partial_rotary_factor`` of each head (element ``i`` paired with ``i +
  rotated / 2``) at YaRN's frequencies (`models.transformer.yarn_inv_freq`)
  with cos and sin times the attention factor, and leaves the rest as it
  is; a ``sliding_attention`` layer turns the whole head at ``theta^(-j /
  (head_dim / 2))``;
* core: `causal_blocked_attention`, the key heads grouped, with
  ``window`` the ``sliding_window`` on a sliding layer (query ``t`` sees
  ``t - window < s <= t``) and None on a full one.

The ``mlp_layer_types`` are a run of ``dense`` blocks (a gated SiLU MLP at
``intermediate_size``) and then ``sparse`` ones: `models.moe.HeldExpertMoE`
with the sigmoid router and its selection bias, the weights normalised
over the chosen and times ``moe_routed_scaling_factor``, and one shared
expert.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp

from fedml_tpu.models.transformer import (
    ArchKeys, causal_blocked_attention, fused_core_fits, rotary,
    window_tiles, yarn_inv_freq)

FULL, SLIDING = "full_attention", "sliding_attention"


@dataclasses.dataclass(frozen=True)
class WindowGQAArch(ArchKeys):
    """Laguna-XS.2's keys (``model_type`` ``laguna``) under their published
    names, ``rope_parameters``' two groups flattened as ``<group>_<key>``
    and the three per-layer lists as tuples, plus the share of a layer
    this chip holds: ``experts_held`` routed experts from ``first_held``
    on and the first ``vocab_held`` rows of the vocabulary.  Layer ``i``
    reads entry ``i`` of each list, so a file cut to fewer layers keeps
    the published lists whole.  The router's weights are normalised over
    the chosen (no published key says so: DeepSeek-V3's rule, whose 256
    experts, top-8 and scaling this model matches); ``initializer_range``
    and ``embedding_range`` as `LatentMoEArch`'s and for its reason."""
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    num_hidden_layers: int
    num_key_value_heads: int
    head_dim: int
    num_experts: int
    num_experts_per_tok: int
    moe_routed_scaling_factor: float
    rms_norm_eps: float
    vocab_size: int
    sliding_window: int
    layer_types: Tuple[str, ...]
    mlp_layer_types: Tuple[str, ...]
    num_attention_heads_per_layer: Tuple[int, ...]
    full_attention_rope_theta: float
    full_attention_factor: float
    full_attention_original_max_position_embeddings: int
    full_attention_beta_fast: float
    full_attention_beta_slow: float
    full_attention_attention_factor: float
    full_attention_partial_rotary_factor: float
    sliding_attention_rope_theta: float
    sliding_attention_partial_rotary_factor: float
    experts_held: int
    first_held: int
    vocab_held: int
    initializer_range: float = 0.02
    embedding_range: float = 1.0

    num_nextn_predict_layers = 0    # no multi-token prediction module

    @classmethod
    def from_dict(cls, d: dict) -> "WindowGQAArch":
        unbuilt = {"attention_bias": False, "tie_word_embeddings": False,
                   "moe_apply_router_weight_on_input": False,
                   "gating": True}
        for key, only in unbuilt.items():
            if d.get(key, only) != only:
                raise NotImplementedError(
                    f"{key} = {d[key]!r}: only {only!r} is built")
        flat = dict(d)
        for group, kind in ((FULL, "yarn"), (SLIDING, "default")):
            rope = (d.get("rope_parameters") or {}).get(group, {})
            if rope.get("rope_type", kind) != kind:
                raise NotImplementedError(
                    f"{group} rotary {rope['rope_type']!r}: only {kind!r} "
                    f"is built")
            flat.update({f"{group}_{k}": v for k, v in rope.items()})
        for key in ("layer_types", "mlp_layer_types",
                    "num_attention_heads_per_layer"):
            if key in d:
                flat[key] = tuple(d[key])
        arch = super().from_dict(flat)
        n = arch.num_hidden_layers
        if any(len(x) < n for x in (arch.layer_types, arch.mlp_layer_types,
                                    arch.num_attention_heads_per_layer)):
            raise ValueError(f"the per-layer lists name fewer than {n} "
                             f"layers")
        kinds = arch.mlp_layer_types[:n]
        if set(arch.layer_types[:n]) - {FULL, SLIDING} or kinds != tuple(
                sorted(kinds)) or set(kinds) - {"dense", "sparse"}:
            raise NotImplementedError(
                f"layers of the kinds {FULL} and {SLIDING}, a run of dense "
                f"blocks and then sparse ones are built")
        if arch.shared_expert_intermediate_size \
                % arch.moe_intermediate_size:
            raise NotImplementedError(
                "a shared expert a whole number of routed ones wide")
        if arch.sliding_attention_partial_rotary_factor != 1:
            raise NotImplementedError("a sliding layer turns its whole head")
        return arch

    @property
    def first_k_dense_replace(self) -> int:
        return self.mlp_layer_types[:self.num_hidden_layers].count("dense")

    def window(self, layer: int) -> Optional[int]:
        """The layer's window of keys, None on a full layer."""
        return (self.sliding_window if self.layer_types[layer] == SLIDING
                else None)

    def attention(self, dtype, block_size, layer):
        return WindowAttention(self, layer, dtype, block_size, name="attn")

    def ffn(self, experts: bool, dtype):
        from fedml_tpu.models.moe import GatedMLP, HeldExpertMoE
        if not experts:
            return GatedMLP(self.intermediate_size, self.initializer_range,
                            dtype, name="mlp")
        return HeldExpertMoE(
            self.num_experts, self.experts_held, self.first_held,
            self.num_experts_per_tok, self.moe_intermediate_size,
            n_shared=(self.shared_expert_intermediate_size
                      // self.moe_intermediate_size),
            scale=self.moe_routed_scaling_factor,
            init_std=self.initializer_range, dtype=dtype, name="moe")

    @property
    def counters(self) -> dict:
        # every attention its core and whether the fused kernels took
        # it, every window core its key tiles, every expert layer its
        # tokens
        n = self.num_hidden_layers
        return {"attn": ("attn_stats", (2,)),
                **({"window": ("window_stats", (2,))}
                   if SLIDING in self.layer_types[:n] else {}),
                **({"moe": ("moe_stats", (5,))}
                   if n > self.first_k_dense_replace else {})}


def full_rotary(a: WindowGQAArch, x, positions):
    """A full layer's rotary: YaRN over the first ``partial_rotary_factor``
    of each head of ``x`` [B, T, H, head_dim], the rest as it is."""
    dim = int(a.head_dim * a.full_attention_partial_rotary_factor)
    freq = yarn_inv_freq(
        dim, a.full_attention_rope_theta, a.full_attention_factor,
        a.full_attention_original_max_position_embeddings,
        a.full_attention_beta_fast, a.full_attention_beta_slow)
    turned = rotary(x[..., :dim], positions, a.full_attention_rope_theta,
                    freq=jnp.asarray(freq),
                    scale=a.full_attention_attention_factor)
    return jnp.concatenate([turned, x[..., dim:]], axis=-1)


class WindowAttention(nn.Module):
    """The attention of layer ``layer`` (the module docstring's).  Sows
    ``attn_stats/calls``, float32 ``[1, fused]`` as `LatentAttention`
    does, and on a sliding layer ``window_stats/tiles``, float32 [causal
    key tiles, key tiles visited] over its sequences and query heads,
    at the blocks of the path that takes the core (`window_tiles`)."""
    arch: WindowGQAArch
    layer: int
    dtype: object = None
    block_size: Optional[int] = None

    @nn.compact
    def __call__(self, x, positions):
        a = self.arch
        b, t, _ = x.shape
        h, kv, d = (a.num_attention_heads_per_layer[self.layer],
                    a.num_key_value_heads, a.head_dim)
        window = a.window(self.layer)

        def dense(n, name):
            return nn.Dense(n, use_bias=False, dtype=self.dtype, name=name,
                            kernel_init=nn.initializers.normal(
                                a.initializer_range))
        q = dense(h * d, "q")(x).reshape(b, t, h, d)
        k = dense(kv * d, "k")(x).reshape(b, t, kv, d)
        v = dense(kv * d, "v")(x).reshape(b, t, kv, d)
        if window is None:
            q, k = full_rotary(a, q, positions), full_rotary(a, k, positions)
        else:
            theta = a.sliding_attention_rope_theta
            q, k = rotary(q, positions, theta), rotary(k, positions, theta)
        fused = fused_core_fits(q, k, v, window=window)
        self.sow("attn_stats", "calls", jnp.array([1.0, float(fused)],
                                                  jnp.float32))
        if window is not None:
            from fedml_tpu.models.fused_attention import BLOCK
            tiles = window_tiles(t, BLOCK if fused else (
                self.block_size or t), window)
            self.sow("window_stats", "tiles",
                     jnp.array(tiles, jnp.float32) * (b * h))
        out = causal_blocked_attention(q, k, v, self.block_size,
                                       window=window)
        return dense(a.hidden_size, "o")(
            out.astype(x.dtype).reshape(b, t, h * d))
