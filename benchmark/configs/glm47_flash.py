"""Plain reference for ``glm47_flash``: GLM-4.7-Flash (``glm4_moe_lite``,
https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json) as one
of eight chips that share each layer holds it, written from the published
keys in flax.linen and ``jax.numpy``, float32.  Nothing of ``fedml_tpu`` is
imported.

**Model.**  Token embedding; ``num_hidden_layers`` pre-norm blocks ``h = x +
Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; a final RMSNorm; an untied
head.  RMSNorm: ``x / sqrt(mean(x^2) + eps) * scale``.  No biases anywhere.

* Attention (multi-head latent attention, DeepSeek-V2 arXiv:2405.04434):
  ``c_q = RMSNorm(x W_qa)`` (rank ``q_lora_rank``), ``q = c_q W_qb`` split by
  head into ``qk_nope_head_dim`` + ``qk_rope_head_dim``; ``x W_kva`` split
  into ``c_kv`` (``kv_lora_rank``, then RMSNorm) and one rotary key ``k_r``
  that all heads share; ``c_kv W_kvb`` split by head into ``k_nope``
  (``qk_nope_head_dim``) and ``v`` (``v_head_dim``).  Rotary (``rope_theta``)
  turns the last ``qk_rope_head_dim`` of each query head and ``k_r``, element
  i paired with element i + half (assumed: the rotate-half convention).
  Each head: causal softmax of ``[q_nope, q_r] . [k_nope, k_r] /
  sqrt(nope + rope)`` over the whole packed sequence (assumed: no document
  mask), times ``v``; heads concatenated through ``W_o``.  Written as a
  loop over heads, each holding its own [T, T] scores.
* FFN of the first ``first_k_dense_replace`` blocks: ``W_down(silu(W_gate
  x) * W_up x)`` at ``intermediate_size``.
* FFN of the other blocks: ``s = sigmoid(x W_r)`` over all
  ``n_routed_experts`` (``topk_method: noaux_tc``, one group); the
  ``num_experts_per_tok`` chosen are the largest of ``s + b`` (``b`` the
  selection bias, which only selects); weights ``s[chosen] / (sum + 1e-20) *
  routed_scaling_factor`` (``norm_topk_prob``); ``y = sum over chosen AND
  HELD experts of w_e Expert_e(x) + Shared(x)``, every expert a gated MLP at
  ``moe_intermediate_size``.  ``experts_held`` experts from ``first_held``
  on live on this chip; what the others would add is their chips' and is
  left out.  Written plainly: every held expert is computed for every token
  and masked by the token's weight for it (zero where it was not chosen),
  which is why the required operations are counted by
  ``forward_macs_per_sample`` and not read from this model's jaxpr.
* Multi-token prediction (``num_nextn_predict_layers`` 0 or 1; assumed: the
  DeepSeek-V3 form, arXiv:2412.19437 section 2.2, weight 0.3):
  ``W_eh [RMSNorm(h_i); RMSNorm(Emb(t_{i+1}))]`` through one more expert
  block, the main model's final norm and head, cross-entropy against
  ``t_{i+2}``; the weighted mean is sown into ``losses``.

Every matrix starts from normal(0, 0.02) (the family's
``initializer_range``), norm scales from 1, the selection bias from
normal(0, 0.02) too (assumed: a trained model's is not zero), and the
token embedding from normal(0, 1) (assumed, ``embedding_range``: at 0.02
the rows are drowned by the first attention's mean value vector, every
position looks alike to the untrained router and all tokens take the same
four experts; a model that is fine-tuned routes by token).  One
`nn.remat` a block and one `jax.checkpoint` a head keep the reference's
backward pass inside a chip's memory; neither changes a value.

Module and parameter names are the ones flax derives the initial values
from, so they are laid out as the system under test lays out its own.

**Data** (``token_shard_arrays`` / ``write_token_shards`` in
``benchmark/token_shards.py``): per silo, packed sequences of token ids.
"""

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

PAD = 0


class _RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                            + self.eps) * scale


def _matrix(name, n, std):
    return nn.Dense(n, use_bias=False, name=name,
                    kernel_init=nn.initializers.normal(std))


def _rotate(x, theta):
    """x [T, r] at positions 0..T-1."""
    t, r = x.shape
    half = r // 2
    freq = theta ** (-np.arange(half, dtype=np.float32) / half)
    angle = np.arange(t, dtype=np.float32)[:, None] * freq[None, :]
    cos, sin = np.cos(angle), np.sin(angle)
    a, b = x[:, :half], x[:, half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@jax.checkpoint
def _head(q, k, v):
    """One head of one sequence: q, k [T, dk], v [T, dv]."""
    t = q.shape[0]
    scores = q @ k.T / math.sqrt(q.shape[-1])
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    scores = jnp.where(seen, scores, -jnp.inf)
    return jax.nn.softmax(scores, axis=-1) @ v


class _Attention(nn.Module):
    m: dict

    @nn.compact
    def __call__(self, x):
        m = self.m
        std, eps = m["initializer_range"], m["rms_norm_eps"]
        heads, nope, rope, dv = (m["num_attention_heads"],
                                 m["qk_nope_head_dim"],
                                 m["qk_rope_head_dim"], m["v_head_dim"])
        rank = m["kv_lora_rank"]
        c_q = _RMSNorm(eps, name="q_norm")(
            _matrix("q_a", m["q_lora_rank"], std)(x))
        q = _matrix("q_b", heads * (nope + rope), std)(c_q)
        ckv = _matrix("kv_a", rank + rope, std)(x)
        c_kv = _RMSNorm(eps, name="kv_norm")(ckv[..., :rank])
        kv = _matrix("kv_b", heads * (nope + dv), std)(c_kv)
        rows = []
        for b in range(x.shape[0]):
            k_r = _rotate(ckv[b, :, rank:], m["rope_theta"])
            outs = []
            for h in range(heads):
                qh = q[b, :, h * (nope + rope):(h + 1) * (nope + rope)]
                kvh = kv[b, :, h * (nope + dv):(h + 1) * (nope + dv)]
                qh = jnp.concatenate(
                    [qh[:, :nope], _rotate(qh[:, nope:], m["rope_theta"])],
                    axis=-1)
                kh = jnp.concatenate([kvh[:, :nope], k_r], axis=-1)
                outs.append(_head(qh, kh, kvh[:, nope:]))
            rows.append(jnp.concatenate(outs, axis=-1))
        return _matrix("o", m["hidden_size"], std)(jnp.stack(rows))


class _GatedMLP(nn.Module):
    width: int
    std: float

    @nn.compact
    def __call__(self, x):
        h = nn.silu(_matrix("gate", self.width, self.std)(x)) \
            * _matrix("up", self.width, self.std)(x)
        return _matrix("down", x.shape[-1], self.std)(h)


class _Experts(nn.Module):
    m: dict

    @nn.compact
    def __call__(self, x):
        m = self.m
        init = nn.initializers.normal(m["initializer_range"])
        d, f = x.shape[-1], m["moe_intermediate_size"]
        total, held, first = (m["n_routed_experts"], m["experts_held"],
                              m["first_held"])
        router = self.param("router", init, (d, total))
        bias = self.param("select_bias", init, (total,))
        w_gate = self.param("experts_gate", init, (held, d, f))
        w_up = self.param("experts_up", init, (held, d, f))
        w_down = self.param("experts_down", init, (held, f, d))
        s = jax.nn.sigmoid(jnp.dot(x, router,
                                   precision=jax.lax.Precision.HIGHEST))
        _, chosen = jax.lax.top_k(s + bias, m["num_experts_per_tok"])
        picked = jnp.sum(jax.nn.one_hot(chosen, total), axis=-2)  # 0 or 1
        w = s * picked
        if m["norm_topk_prob"]:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        w = w * m["routed_scaling_factor"]
        y = _GatedMLP(m["n_shared_experts"] * f, m["initializer_range"],
                      name="shared")(x)
        for e in range(held):
            out = (nn.silu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e]
            y = y + out * w[..., first + e, None]
        return y


class _Block(nn.Module):
    m: dict
    experts: bool

    @nn.compact
    def __call__(self, x):
        m = self.m
        eps = m["rms_norm_eps"]
        h = x + _Attention(m, name="attn")(_RMSNorm(eps, name="attn_norm")(x))
        f = _RMSNorm(eps, name="ffn_norm")(h)
        if self.experts:
            return h + _Experts(m, name="moe")(f)
        return h + _GatedMLP(m["intermediate_size"], m["initializer_range"],
                             name="mlp")(f)


class Model(nn.Module):
    m: dict

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        m = self.m
        std, eps = m["initializer_range"], m["rms_norm_eps"]
        embed = nn.Embed(m["vocab_held"], m["hidden_size"], name="tok_embed",
                         embedding_init=nn.initializers.normal(
                             m["embedding_range"]))
        final_norm = _RMSNorm(eps, name="final_norm")
        head = _matrix("lm_head", m["vocab_held"], std)
        block = nn.remat(_Block)
        x = embed(tokens)
        for i in range(m["num_hidden_layers"]):
            x = block(m, i >= m["first_k_dense_replace"],
                      name=f"layer_{i}")(x)
        logits = head(final_norm(x))
        if m["num_nextn_predict_layers"] and tokens.shape[1] >= 3:
            merged = jnp.concatenate(
                [_RMSNorm(eps, name="mtp_hnorm")(x[:, :-1]),
                 _RMSNorm(eps, name="mtp_enorm")(embed(tokens[:, 1:]))],
                axis=-1)
            y = block(m, True, name="mtp_block")(
                _matrix("mtp_proj", m["hidden_size"], std)(merged))
            logp = jax.nn.log_softmax(head(final_norm(y))[:, :-1], axis=-1)
            target = tokens[:, 2:]
            real = (target != PAD).astype(jnp.float32)
            nll = -jnp.take_along_axis(logp, target[..., None], axis=-1)[..., 0]
            self.sow("losses", "mtp", m["mtp_loss_weight"] * jnp.sum(
                nll * real) / jnp.maximum(jnp.sum(real), 1.0))
        return logits


class _Frozen(dict):
    """A configuration's ``model`` keys, hashable so that flax takes it as
    a module's field."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def build_model(config: dict) -> nn.Module:
    m = {"initializer_range": 0.02, "mtp_loss_weight": 0.3,
         "embedding_range": 1.0, **config["model"]}
    return Model(_Frozen(m))


def train_clients(arrays: dict, config: dict, program_seed: int):
    """Per-silo (x [n, T], y [n, T]) int32: a packed sequence of T + 1 ids
    gives ``x = ids[:-1]``, ``y = ids[1:]``.

    The harness asks for these once the program's call has returned and
    before the reference starts.  What that call left unreachable but
    uncollected (an engine in reference cycles, GB-size arrays inside it)
    is collected here, so that the reference and the comparison, which
    hold eighteen trees between them, have the host's memory and the
    chip's to themselves."""
    import ctypes
    import gc
    gc.collect()
    # ... and what the allocator holds of it (5 GB of freed heap after
    # the program's compiles, my chip run, PR 37) goes back to the machine
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass
    return [(np.ascontiguousarray(s[:, :-1]), np.ascontiguousarray(s[:, 1:]))
            for s in arrays["train"]]


def forward_macs_per_sample(config: dict, sample_shape) -> float:
    """Multiply-accumulates the forward pass of one sequence of T tokens
    REQUIRES.  Per token: the five attention projections, the scores and
    the mix against the positions up to its own (the causal half: (T + 1)
    / 2 on average), the dense block's three matrices, and per expert
    block the router, the shared expert and the experts a token is sent
    to AND this chip holds (``num_experts_per_tok * experts_held /
    n_routed_experts`` of them at even routing); the head.  The embedding
    is a lookup.  What the plain model above computes and masks away
    (seven of eight held experts a token, the scores above the diagonal)
    does not count."""
    m = config["model"]
    (t,) = sample_shape
    d, heads = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    attn = (d * m["q_lora_rank"] + m["q_lora_rank"] * heads * qk
            + d * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            + m["kv_lora_rank"] * heads
            * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + heads * m["v_head_dim"] * d)
    scores = heads * (qk + m["v_head_dim"]) * (t + 1) / 2.0
    dense = 3 * d * m["intermediate_size"]
    expert = 3 * d * m["moe_intermediate_size"]
    routed = (m["num_experts_per_tok"] * m["experts_held"]
              / m["n_routed_experts"])
    moe = (d * m["n_routed_experts"] + m["n_shared_experts"] * expert
           + routed * expert)
    n_dense = m["first_k_dense_replace"]
    n_moe = m["num_hidden_layers"] - n_dense
    per_token = ((n_dense + n_moe) * (attn + scores) + n_dense * dense
                 + n_moe * moe + d * m["vocab_held"])
    if m["num_nextn_predict_layers"]:
        per_token += (attn + scores + moe + 2 * d * d + d * m["vocab_held"])
    return float(t * per_token)
