"""Plain reference for ``keye_vl2_30b_a3b``: the language model of
Keye-VL-2.0-30B-A3B (``KeyeVL2``,
https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json)
as one of sixteen chips that share each layer holds it, written from the
published keys in flax.linen and ``jax.numpy``, float32.  Nothing of
``fedml_tpu`` is imported.  The vision tower is not here: its sizes are in
no file on this machine, and the language model takes what it would give
the positions, three rows of them, as an input.

**Model.**  Token embedding; ``num_hidden_layers`` pre-norm blocks ``h = x +
Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``, every one an expert layer;
a final RMSNorm; an untied head.  No biases but the indexer's LayerNorm's.
With ``u = RMSNorm(x)`` [T, hidden]:

* Heads: ``q = u W_q`` [T, heads, head_dim], ``k = u W_k``, ``v = u W_v``
  [T, kv heads, head_dim]; ``q`` and ``k`` each through an RMSNorm over
  the head width (assumed: Qwen3-MoE's); query head ``h`` uses key/value
  head ``h // (heads / kv heads)``.
* Rotary, three axes (``rope_scaling.mrope_section`` [16, 24, 24];
  assumed: Qwen2-VL's chunked layout): ``positions`` is [3, T] (temporal,
  height, width); of a head's ``head_dim / 2`` frequencies ``theta^(-j /
  half)`` the first 16 turn by the temporal position, the next 24 by the
  height, the last 24 by the width, element ``i`` paired with ``i + half``.
  Text gives all three rows the token index, which is the default.
* Indexer (``sa_config``; DeepSeek-V3.2-Exp's lightning indexer): ``qI =
  rot(u W_qI)`` [T, 16, 64], ``kI = rot(LayerNorm(u W_kI))`` [T, 64] (one
  key head), ``w = (u W_w) * 16^-1/2 * 64^-1/2``; ``I[t, s] = sum_j w[t, j]
  relu(qI[t, j] . kI[s])`` for ``s <= t``, accumulated head by head (a
  `jax.lax.scan`); rotary over the whole 64 by the temporal position.
* Selection: the keys ``s <= t`` with the ``topk`` largest ``I[t, .]``
  (every ``s <= t`` while ``t < topk``), by `jax.lax.top_k`, whose ties go
  to the smaller ``s`` (`_select`).  Integers: no gradient passes, and the indexer's five
  leaves a layer receive none (assumed: a fine-tune with the indexer
  frozen; the loss that trains one is not in the published file).
* Core: each head the softmax over the selected ``s`` of ``q . k /
  sqrt(head_dim)``, times ``v``; the heads one after another (a
  `jax.lax.map`: one compiled body, which a Python loop over 32 heads in
  each layer made 32 of), each holding its own [T, T] scores; heads
  concatenated through ``W_o``.
* Experts: ``p = softmax(x W_r)`` over all ``num_experts``; the
  ``num_experts_per_tok`` largest; weights ``p[chosen] / sum(p[chosen])``
  (``norm_topk_prob``); ``y = sum over chosen AND HELD experts of w_e
  W_down_e(silu(W_gate_e x) * W_up_e x)``.  ``experts_held`` experts from
  ``first_held`` on live on this chip; what the others would add is their
  chips' and is left out.  Every held expert is computed for every token
  and masked by the token's weight for it, which is why the required
  operations are counted by ``forward_macs_per_sample`` and not read from
  this model's jaxpr.  No shared expert, no selection bias.

Every matrix starts from normal(0, 0.02), norm scales from 1 (the
LayerNorm's bias from 0) and the token embedding from normal(0, 1), as
``glm47_flash.py`` beside this file and for its reason.  One `nn.remat` a
block and one `jax.checkpoint` a head keep the backward pass inside a
chip's memory; neither changes a value.

Module and parameter names are the ones flax derives the initial values
from, so they are laid out as the system under test lays out its own.
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


class _LayerNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],))
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        return (x - mean) / jnp.sqrt(var + self.eps) * scale + bias


def _matrix(name, n, std):
    return nn.Dense(n, use_bias=False, name=name,
                    kernel_init=nn.initializers.normal(std))


def _rotate(x, positions, theta, sections=None):
    """x [T, r].  ``positions`` [T] turns every frequency; [3, T] with
    ``sections`` turns the first ``sections[0]`` frequencies by row 0, the
    next ``sections[1]`` by row 1, the rest by row 2."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if sections is None:
        at = positions[:, None]
    else:
        row = np.repeat(np.arange(len(sections)), sections)     # [half]
        at = positions[row].T                                   # [T, half]
    angle = at.astype(jnp.float32) * freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[:, :half], x[:, half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _select(index, topk):
    """[T, T] bool: for query t the ``topk`` keys s <= t of largest
    ``index[t, s]``, all of them while t < topk.  `jax.lax.top_k` gives a
    query's ``topk``-th largest score; the keys above it are taken and,
    of those equal to it, the first few that are still wanted: the order
    `top_k` itself puts equal scores in (the smaller index first).  (No
    scatter of `top_k`'s indices: 16.7 M single writes a layer take the
    chip seconds.)"""
    t = index.shape[0]
    k = min(topk, t)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    masked = jnp.where(causal, index, -jnp.inf)
    kth = jax.lax.top_k(masked, k)[0][:, -1:]
    above = masked > kth
    equal = (masked == kth) & causal
    wanted = k - jnp.sum(above, axis=-1, keepdims=True)
    return above | (equal & (jnp.cumsum(equal, axis=-1) <= wanted))


@jax.checkpoint
def _head(q, k, v, selected):
    """One head of one sequence: q, k, v [T, d], selected [T, T]."""
    scores = q @ k.T / math.sqrt(q.shape[-1])
    scores = jnp.where(selected, scores, -jnp.inf)
    return jax.nn.softmax(scores, axis=-1) @ v


class _Attention(nn.Module):
    m: dict

    @nn.compact
    def __call__(self, x, positions):
        m = self.m
        std, eps, theta = (m["initializer_range"], m["rms_norm_eps"],
                           m["rope_theta"])
        heads, kv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                        m["head_dim"])
        sa = m["sa_config"]
        ih, idim = sa["indexer_num_heads"], sa["indexer_head_dim"]
        sections = m["rope_scaling"]["mrope_section"]
        b, t, _ = x.shape
        q = _RMSNorm(eps, name="q_norm")(
            _matrix("q", heads * d, std)(x).reshape(b, t, heads, d))
        k = _RMSNorm(eps, name="k_norm")(
            _matrix("k", kv * d, std)(x).reshape(b, t, kv, d))
        v = _matrix("v", kv * d, std)(x).reshape(b, t, kv, d)
        q_i = _matrix("idx_q", ih * idim, std)(x).reshape(b, t, ih, idim)
        k_i = _LayerNorm(name="idx_k_norm")(_matrix("idx_k", idim, std)(x))
        w_i = _matrix("idx_w", ih, std)(x) * (ih ** -0.5 * idim ** -0.5)
        group = heads // kv     # query heads a key/value head serves
        time = positions[0]
        rows = []
        for s in range(b):
            key = _rotate(k_i[s], time, theta)

            def add_head(index, head):      # the indexer's heads in turn
                q_j, w_j = head
                return index + w_j[:, None] * nn.relu(
                    _rotate(q_j, time, theta) @ key.T), None
            index, _ = jax.lax.scan(
                add_head, jnp.zeros((t, t), jnp.float32),
                (q_i[s].transpose(1, 0, 2), w_i[s].T))
            selected = _select(index, sa["topk"])
            keys = jnp.stack([_rotate(k[s, :, g], positions, theta, sections)
                              for g in range(kv)])
            values = v[s].transpose(1, 0, 2)

            def one_head(head):             # the heads one after another
                q_h, g = head
                return _head(_rotate(q_h, positions, theta, sections),
                             keys[g], values[g], selected)
            outs = jax.lax.map(one_head, (q[s].transpose(1, 0, 2),
                                          jnp.arange(heads) // group))
            rows.append(outs.transpose(1, 0, 2).reshape(t, heads * d))
        return _matrix("o", m["hidden_size"], std)(jnp.stack(rows))


class _Experts(nn.Module):
    m: dict

    @nn.compact
    def __call__(self, x):
        m = self.m
        init = nn.initializers.normal(m["initializer_range"])
        d, f = x.shape[-1], m["moe_intermediate_size"]
        total, held, first = (m["num_experts"], m["experts_held"],
                              m["first_held"])
        router = self.param("router", init, (d, total))
        w_gate = self.param("experts_gate", init, (held, d, f))
        w_up = self.param("experts_up", init, (held, d, f))
        w_down = self.param("experts_down", init, (held, f, d))
        p = jax.nn.softmax(jnp.dot(x, router,
                                   precision=jax.lax.Precision.HIGHEST))
        _, chosen = jax.lax.top_k(p, m["num_experts_per_tok"])
        picked = jnp.sum(jax.nn.one_hot(chosen, total), axis=-2)  # 0 or 1
        w = p * picked
        if m["norm_topk_prob"]:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        y = jnp.zeros_like(x)
        for e in range(held):
            out = (nn.silu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e]
            y = y + out * w[..., first + e, None]
        return y


class _Block(nn.Module):
    m: dict

    @nn.compact
    def __call__(self, x, positions):
        eps = self.m["rms_norm_eps"]
        h = x + _Attention(self.m, name="attn")(
            _RMSNorm(eps, name="attn_norm")(x), positions)
        return h + _Experts(self.m, name="moe")(
            _RMSNorm(eps, name="ffn_norm")(h))


class Model(nn.Module):
    m: dict

    @nn.compact
    def __call__(self, tokens, train: bool = False, positions=None):
        m = self.m
        t = tokens.shape[1]
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(t), (3, t))
        x = nn.Embed(m["vocab_held"], m["hidden_size"], name="tok_embed",
                     embedding_init=nn.initializers.normal(
                         m["embedding_range"]))(tokens)
        block = nn.remat(_Block)
        for i in range(m["num_hidden_layers"]):
            x = block(m, name=f"layer_{i}")(x, positions)
        x = _RMSNorm(m["rms_norm_eps"], name="final_norm")(x)
        return _matrix("lm_head", m["vocab_held"], m["initializer_range"])(x)


class _Frozen(dict):
    """A configuration's ``model`` keys, hashable so that flax takes it as
    a module's field."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def build_model(config: dict) -> nn.Module:
    m = {"initializer_range": 0.02, "embedding_range": 1.0,
         **config["model"]}
    return Model(_Frozen(m))


def train_clients(arrays: dict, config: dict, program_seed: int):
    """Per-silo (x [n, T], y [n, T]) int32: a packed sequence of T + 1 ids
    gives ``x = ids[:-1]``, ``y = ids[1:]``.

    As ``glm47_flash.py``'s: the harness asks for these once the program's
    call has returned and before the reference starts, so what that call
    left unreachable but uncollected is collected here and the
    allocator's freed heap handed back: the reference and the comparison
    hold eighteen trees between them."""
    import ctypes
    import gc
    gc.collect()
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass
    return [(np.ascontiguousarray(s[:, :-1]), np.ascontiguousarray(s[:, 1:]))
            for s in arrays["train"]]


def selected_pairs(t: int, topk: int) -> int:
    """(query, key) pairs a sequence of ``t`` positions selects: ``min(i +
    1, topk)`` for the query at ``i``."""
    full = min(t, topk)
    return full * (full + 1) // 2 + (t - full) * topk


def forward_macs_per_sample(config: dict, sample_shape) -> float:
    """Multiply-accumulates the forward pass of one sequence of T tokens
    REQUIRES.  Per token and layer: the four attention projections, the
    indexer's three, the index scores against the positions up to its own
    (the causal half), the scores and the mix against the positions it
    SELECTS, the router, and the experts a token is sent to AND this chip
    holds (``num_experts_per_tok * experts_held / num_experts`` of them at
    even routing); the head.  The embedding is a lookup.  What the plain
    model above computes and masks away (seven of eight held experts a
    token, the scores of pairs not selected) does not count."""
    m = config["model"]
    (t,) = sample_shape
    d, heads, kv, hd = (m["hidden_size"], m["num_attention_heads"],
                        m["num_key_value_heads"], m["head_dim"])
    sa = m["sa_config"]
    ih, idim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    attn = d * hd * (2 * heads + 2 * kv)
    indexer = d * (ih * idim + idim + ih)
    core = heads * 2 * hd * selected_pairs(t, sa["topk"]) / t
    index = ih * idim * (t + 1) / 2.0
    expert = 3 * d * m["moe_intermediate_size"]
    routed = (m["num_experts_per_tok"] * m["experts_held"]
              / m["num_experts"])
    moe = d * m["num_experts"] + routed * expert
    per_token = (m["num_hidden_layers"] * (attn + indexer + core + index
                                           + moe) + d * m["vocab_held"])
    return float(t * per_token)
