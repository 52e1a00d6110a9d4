"""Plain reference for ``laguna_xs2``: Laguna-XS.2 (``laguna``,
https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json) as one of
thirty-two chips that share each layer holds it, written from the published
keys in flax.linen and ``jax.numpy``, float32.  Nothing of ``fedml_tpu`` is
imported (of the benchmark, the count of pairs inside a window).

**Model.**  Token embedding; ``num_hidden_layers`` pre-norm blocks ``h = x +
Attn_i(RMSNorm(x))``, ``y = h + FFN_i(RMSNorm(h))``; a final RMSNorm; an
untied head.  No biases.  Layer ``i`` is of the kind ``layer_types[i]``
(``full_attention`` or ``sliding_attention``) with
``num_attention_heads_per_layer[i]`` query heads, and its FFN is
``mlp_layer_types[i]`` (``dense`` or ``sparse``).  With ``u = RMSNorm(x)``
[T, hidden]:

* Heads: ``q = u W_q`` [T, heads, 128], ``k = u W_k``, ``v = u W_v`` [T, 8,
  128], no norms on q or k (assumed: no key names one); query head ``h``
  uses key/value head ``h // (heads / 8)``.
* Rotary, by the layer's kind (``rope_parameters``).  Full layers: YaRN
  (Peng et al., arXiv:2309.00071, as transformers' ``_compute_yarn_parameters``
  computes it, ``truncate`` on) over the first ``partial_rotary_factor *
  128 = 64`` elements of each head, element i paired with element i + 32;
  the frequencies ``theta^(-2j / 64)`` kept where they turn more than
  ``beta_fast`` times over ``original_max_position_embeddings``, divided by
  ``factor`` where they turn fewer than ``beta_slow`` times, ramped between;
  cos and sin times ``attention_factor``; elements 64-127 unturned.
  Sliding layers: ``theta^(-j / 64)`` over the whole head, element i paired
  with i + 64.
* Core, full layers: each head the causal softmax of ``q . k / sqrt(128)``
  over the whole packed sequence (assumed: no document mask), times ``v``;
  the heads one after another (a `jax.lax.map`), each holding its own [T,
  T] scores.  Sliding layers: query ``t`` sees the keys ``t - window < s <=
  t`` (assumed: the transformers sliding-window mask); each block of
  ``window`` queries against the ``2 window - 1`` positions that end at its
  last query (`_window_core`), every head at once, the blocks one after
  another.  Heads concatenated through ``W_o``.
* Dense FFN: ``W_down(silu(W_gate x) * W_up x)`` at ``intermediate_size``
  (assumed: ``gating: true`` is this gated SiLU MLP; the row has no
  ``hidden_act``).
* Sparse FFN: ``s = sigmoid(x W_r)`` over all ``num_experts``; the
  ``num_experts_per_tok`` chosen are the largest of ``s + b`` (``b`` the
  selection bias, which only selects); weights ``s[chosen] / (sum + 1e-20)
  * moe_routed_scaling_factor`` (assumed: DeepSeek-V3's router, whose
  sizes this row matches); ``y = sum over chosen AND HELD experts of w_e
  Expert_e(x) + Shared(x)``, every expert a gated SiLU MLP at
  ``moe_intermediate_size``, the shared one at
  ``shared_expert_intermediate_size``.  ``experts_held`` experts from
  ``first_held`` on live on this chip; what the others would add is their
  chips' and is left out.  Every held expert is computed for every token
  and masked by the token's weight for it, which is why the required
  operations are counted by ``forward_macs_per_sample`` and not read from
  this model's jaxpr.

Every matrix starts from normal(0, 0.02), norm scales from 1, the selection
bias from normal(0, 0.02) and the token embedding from normal(0, 1), as
``glm47_flash.py`` beside this file and for its reasons.  One `nn.remat` a
block, one `jax.checkpoint` a head and one a window block keep the backward
pass inside a chip's memory; none changes a value.

Module and parameter names are the ones flax derives the initial values
from, so they are laid out as the system under test lays out its own.
"""

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from benchmark.window_attention import window_pairs

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


def _yarn(dim, rope):
    """YaRN's inverse frequencies [dim / 2] for the ``rope_parameters``
    group ``rope``, float32, step by step as transformers makes them."""
    base, factor = rope["rope_theta"], rope["factor"]
    original = rope["original_max_position_embeddings"]

    def correction(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))) \
            / (2 * math.log(base))
    low = max(math.floor(correction(rope["beta_fast"])), 0)
    high = min(math.ceil(correction(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = np.float32(base) ** (np.arange(0, dim, 2, dtype=np.float32)
                                     / np.float32(dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - np.float32(low))
                   / np.float32(high - low), 0, 1)
    extrapolation = np.float32(1) - ramp
    return ((np.float32(1) / (np.float32(factor) * pos_freqs))
            * (1 - extrapolation)
            + (np.float32(1) / pos_freqs) * extrapolation).astype(np.float32)


def _rotate(x, freq, scale=1.0):
    """x [T, ..., r] at positions 0..T-1, element i paired with i + r/2,
    turned at ``freq`` [r/2]; cos and sin times ``scale``."""
    t, half = x.shape[0], x.shape[-1] // 2
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(freq)
    cos, sin = jnp.cos(angle) * scale, jnp.sin(angle) * scale
    shape = (t,) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _turn(x, kind, m):
    """A layer's rotary on ``x`` [T, heads, 128]."""
    rope = m["rope_parameters"][kind]
    d = x.shape[-1]
    if kind == "sliding_attention":
        half = d // 2
        freq = rope["rope_theta"] ** (-np.arange(half, dtype=np.float32)
                                      / half)
        return _rotate(x, freq)
    dim = int(d * rope["partial_rotary_factor"])
    turned = _rotate(x[..., :dim], _yarn(dim, rope),
                     rope["attention_factor"])
    return jnp.concatenate([turned, x[..., dim:]], axis=-1)


@jax.checkpoint
def _head(q, k, v):
    """One head of one sequence, causal: q, k, v [T, d]."""
    t = q.shape[0]
    scores = q @ k.T / math.sqrt(q.shape[-1])
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    scores = jnp.where(seen, scores, -jnp.inf)
    return jax.nn.softmax(scores, axis=-1) @ v


def _window_core(q, k, v, window):
    """One sequence under a window: q [T, heads, d], k, v [T, heads, d]
    (the key heads repeated for their query heads).  Query t sees the keys
    ``t - window < s <= t``.  Queries go a block of ``window`` at a time
    against the ``2 window - 1`` positions that end at the block's last
    query (those before position 0 and after T - 1 are zeros, never
    seen)."""
    t = q.shape[0]
    n = -(-t // window)
    tail = n * window - t
    qp = jnp.pad(q, ((0, tail), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((window - 1, tail), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((window - 1, tail), (0, 0), (0, 0)))
    lag = (np.arange(window)[:, None] + window - 1
           - np.arange(2 * window - 1)[None, :])        # query - key

    @jax.checkpoint
    def block(i):
        lo = i * window
        qb = jax.lax.dynamic_slice_in_dim(qp, lo, window)
        kb = jax.lax.dynamic_slice_in_dim(kp, lo, 2 * window - 1)
        vb = jax.lax.dynamic_slice_in_dim(vp, lo, 2 * window - 1)
        key_pos = lo - (window - 1) + np.arange(2 * window - 1)
        seen = (lag >= 0) & (lag < window) & (key_pos >= 0)[None, :]
        scores = jnp.einsum("qhd,khd->hqk", qb, kb) / math.sqrt(q.shape[-1])
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1),
                          vb)
    out = jax.lax.map(block, jnp.arange(n))      # [n, window, heads, d]
    return out.reshape(n * window, *q.shape[1:])[:t]


class _Attention(nn.Module):
    m: dict
    layer: int

    @nn.compact
    def __call__(self, x):
        m = self.m
        std = m["initializer_range"]
        kind = m["layer_types"][self.layer]
        heads = m["num_attention_heads_per_layer"][self.layer]
        kv, d = m["num_key_value_heads"], m["head_dim"]
        b, t, _ = x.shape
        q = _matrix("q", heads * d, std)(x).reshape(b, t, heads, d)
        k = _matrix("k", kv * d, std)(x).reshape(b, t, kv, d)
        v = _matrix("v", kv * d, std)(x).reshape(b, t, kv, d)
        group = heads // kv     # query heads a key/value head serves
        rows = []
        for s in range(b):
            qs, ks = _turn(q[s], kind, m), _turn(k[s], kind, m)
            if kind == "sliding_attention":
                out = _window_core(qs, jnp.repeat(ks, group, axis=1),
                                   jnp.repeat(v[s], group, axis=1),
                                   m["sliding_window"])
            else:
                keys, values = ks.transpose(1, 0, 2), v[s].transpose(1, 0, 2)

                def one_head(head):         # the heads one after another
                    q_h, g = head
                    return _head(q_h, keys[g], values[g])
                out = jax.lax.map(one_head, (qs.transpose(1, 0, 2),
                                             jnp.arange(heads) // group))
                out = out.transpose(1, 0, 2)
            rows.append(out.reshape(t, heads * d))
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
        total, held, first = (m["num_experts"], m["experts_held"],
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
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        w = w * m["moe_routed_scaling_factor"]
        y = _GatedMLP(m["shared_expert_intermediate_size"],
                      m["initializer_range"], name="shared")(x)
        for e in range(held):
            out = (nn.silu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e]
            y = y + out * w[..., first + e, None]
        return y


class _Block(nn.Module):
    m: dict
    layer: int

    @nn.compact
    def __call__(self, x):
        m = self.m
        eps = m["rms_norm_eps"]
        h = x + _Attention(m, self.layer, name="attn")(
            _RMSNorm(eps, name="attn_norm")(x))
        f = _RMSNorm(eps, name="ffn_norm")(h)
        if m["mlp_layer_types"][self.layer] == "sparse":
            return h + _Experts(m, name="moe")(f)
        return h + _GatedMLP(m["intermediate_size"], m["initializer_range"],
                             name="mlp")(f)


class Model(nn.Module):
    m: dict

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        m = self.m
        x = nn.Embed(m["vocab_held"], m["hidden_size"], name="tok_embed",
                     embedding_init=nn.initializers.normal(
                         m["embedding_range"]))(tokens)
        block = nn.remat(_Block)
        for i in range(m["num_hidden_layers"]):
            x = block(m, i, name=f"layer_{i}")(x)
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
    allocator's freed heap handed back."""
    import ctypes
    import gc
    gc.collect()
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass
    return [(np.ascontiguousarray(s[:, :-1]), np.ascontiguousarray(s[:, 1:]))
            for s in arrays["train"]]


def forward_macs_per_sample(config: dict, sample_shape) -> float:
    """Multiply-accumulates the forward pass of one sequence of T tokens
    REQUIRES.  Per token and layer: the four attention projections at the
    layer's head count, the scores and the mix against the positions the
    token sees (the causal half on a full layer, its window on a sliding
    one), and the dense MLP or the router, the shared expert and the
    experts a token is sent to AND this chip holds
    (``num_experts_per_tok * experts_held / num_experts`` of them at even
    routing); the head.  The embedding is a lookup.  What the plain model
    above computes and masks away (seven of eight held experts a token,
    the scores above the diagonal or outside the window) does not
    count."""
    m = config["model"]
    (t,) = sample_shape
    d, kv, hd = m["hidden_size"], m["num_key_value_heads"], m["head_dim"]
    expert = 3 * d * m["moe_intermediate_size"]
    routed = (m["num_experts_per_tok"] * m["experts_held"]
              / m["num_experts"])
    per_token = d * m["vocab_held"]
    for i in range(m["num_hidden_layers"]):
        heads = m["num_attention_heads_per_layer"][i]
        pairs = (window_pairs(t, m["sliding_window"])
                 if m["layer_types"][i] == "sliding_attention"
                 else t * (t + 1) // 2)
        per_token += d * hd * (2 * heads + 2 * kv) + heads * 2 * hd * pairs / t
        if m["mlp_layer_types"][i] == "sparse":
            per_token += (d * m["num_experts"]
                          + 3 * d * m["shared_expert_intermediate_size"]
                          + routed * expert)
        else:
            per_token += 3 * d * m["intermediate_size"]
    return float(t * per_token)
