"""Everything the test-only configuration ``fed_shakespeare_moe`` brings, in
one file, as a later language-model configuration would: its seeded data and
their writer, the reference's own view of them, the plain reference model
and the count of operations the model requires.  Nothing of ``fedml_tpu`` is
imported.

**Data.**  Federated Shakespeare as TFF exports it and FedML reads it
(``fed_shakespeare/utils.py``): every client holds ``snippets`` of text; a
snippet becomes ``<bos> characters <eos>`` over the tutorial's 86-character
vocabulary (pad 0, the characters 1..86, bos 87, eos 88, any other
character 89: 90 ids), is cut into windows of 81 ids, the last one filled
with pads (a window of fewer than two ids is dropped), and a window gives
``x = window[:-1]``, ``y = window[1:]``.  The generator writes no
Shakespeare: a client's text is words drawn from a seeded list of 40
made-up words, so that a character model learns on it within a few rounds.
Every seed has the same snippet lengths (spread evenly between the two
bounds, dealt to the clients in a seeded order), so the same number of
windows and of padded steps.

**Model.**  A decoder of ``n_layers`` pre-LayerNorm blocks at width
``d_model`` with learned position embeddings: causal attention over
``n_heads`` heads, then either a GELU MLP of width ``d_ff`` or, with
``experts`` > 0, the Switch layer of Fedus et al. 2021 (arXiv:2101.03961,
section 2): a softmax router picks one expert a token, an expert takes at
most ``ceil(capacity_factor * tokens / experts)`` tokens of a batch in the
order they stand and the rest pass by on the residual, the expert's output
is scaled by the router's probability, and ``experts * sum_e f_e * P_e``
(equation 4; f the share of tokens sent to an expert, P the router's mean
probability of it) is sown into ``losses`` at ``aux_weight`` for every such
layer.  Pad tokens are not routed and count in neither share.  Written
plainly: every expert is computed for every token and all but the chosen
one are masked away, which is why the model's required operations are
counted by ``forward_macs_per_sample`` below and not read from this
model's jaxpr.

Module and parameter names are the ones flax derives the initial values
from, so they are laid out as the system under test lays out its own.
"""

import math
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

# the TFF text-generation tutorial's vocabulary, in its published order
CHARS = ('dhlptx@DHLPTX $(,048cgkoswCGKOSW[_#\'/37;?bfjnrvzBFJNRVZ"&*.26:'
         '\naeimquyAEIMQUY]!%)-159\r')
PAD, BOS, EOS, OOV = 0, len(CHARS) + 1, len(CHARS) + 2, len(CHARS) + 3
VOCAB = len(CHARS) + 4
WINDOW = 81


# -- data ----------------------------------------------------------------------

def fed_shakespeare_arrays(seed: int, clients: int, snippets: int,
                           min_chars: int, max_chars: int) -> dict:
    """``{"train": [[snippet, ...] per client], "test": [...]}``: every
    client ``snippets`` snippets, their lengths spread evenly over
    [min_chars, max_chars] across the population."""
    rng = np.random.default_rng([seed, 0x5A4E])
    letters = np.asarray(list("etaoinshrdlucmfwyp"))
    words = ["".join(rng.choice(letters, size=rng.integers(2, 8)))
             for _ in range(40)]
    weight = rng.dirichlet(np.full(len(words), 0.5))

    def text(chars: int) -> str:
        out = ""
        while len(out) < chars:
            out += words[rng.choice(len(words), p=weight)] + " "
        return out[:chars]

    n = clients * snippets
    lengths = min_chars + (np.arange(n) * (max_chars - min_chars)
                           // max(n - 1, 1))
    out = {}
    for split, scale in (("train", 1.0), ("test", 0.25)):
        dealt = rng.permutation(lengths).reshape(clients, snippets)
        out[split] = [[text(max(int(c * scale), 2)) for c in row]
                      for row in dealt]
    return out


def write_fed_shakespeare_h5(arrays: dict, out_dir: str) -> None:
    """``shakespeare_{train,test}.h5`` in the TFF export's layout:
    ``examples/<client>/snippets``, an array of byte strings."""
    import h5py
    os.makedirs(out_dir, exist_ok=True)
    for split in ("train", "test"):
        path = os.path.join(out_dir, f"shakespeare_{split}.h5")
        with h5py.File(path + ".tmp", "w") as f:
            ex = f.create_group("examples")
            for c, snips in enumerate(arrays[split]):
                ex.create_group(f"s{c:05d}").create_dataset(
                    "snippets", data=[s.encode("utf8") for s in snips])
        os.replace(path + ".tmp", path)


def windows(snippet: str) -> list:
    ids = ([BOS] + [CHARS.find(c) + 1 or OOV for c in snippet] + [EOS])
    out = []
    for lo in range(0, len(ids), WINDOW):
        win = ids[lo:lo + WINDOW]
        if len(win) >= 2:
            out.append(win + [PAD] * (WINDOW - len(win)))
    return out


def train_clients(arrays: dict, config: dict, program_seed: int):
    """Per-client (x [n, 80], y [n, 80]) int32 of the training split, in
    population order (h5 groups iterate by name; the writer names them in
    order)."""
    out = []
    for snips in arrays["train"]:
        w = np.asarray([w for s in snips for w in windows(s)],
                       np.int32).reshape(-1, WINDOW)
        out.append((w[:, :-1], w[:, 1:]))
    return out


# -- model ---------------------------------------------------------------------

class _Attention(nn.Module):
    heads: int
    width: int

    @nn.compact
    def __call__(self, x):
        t, d = x.shape[1], self.width // self.heads
        q, k, v = (nn.DenseGeneral((self.heads, d), name=n)(x)
                   for n in ("query", "key", "value"))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
        causal = jnp.tril(jnp.ones((t, t), bool))
        p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        return nn.DenseGeneral(self.width, axis=(-2, -1), name="out")(out)


class _Switch(nn.Module):
    experts: int
    width: int
    hidden: int
    capacity_factor: float
    aux_weight: float

    @nn.compact
    def __call__(self, x, real):
        b, t, d = x.shape
        e, g = self.experts, b * t     # a batch's tokens queue together
        cap = max(1, math.ceil(self.capacity_factor * g / e))
        x = x.reshape(1, g, d)
        real = real.reshape(1, g).astype(jnp.float32)
        probs = jax.nn.softmax(nn.Dense(e, name="router")(x), axis=-1)
        chosen = jax.nn.one_hot(jnp.argmax(probs, -1), e) * real[..., None]
        n_real = jnp.maximum(jnp.sum(real), 1.0)
        f = jnp.sum(chosen, axis=(0, 1)) / n_real
        p = jnp.sum(probs * real[..., None], axis=(0, 1)) / n_real
        self.sow("losses", "load_balance",
                 self.aux_weight * e * jnp.sum(f * p))
        # a token's place in its expert's queue
        place = jnp.sum((jnp.cumsum(chosen, axis=1) - 1.0) * chosen, axis=-1)
        taken = chosen * (place < cap)[..., None]
        w1 = self.param("w1", nn.initializers.lecun_normal(),
                        (e, d, self.hidden))
        b1 = self.param("b1", nn.initializers.zeros, (e, self.hidden))
        w2 = self.param("w2", nn.initializers.lecun_normal(),
                        (e, self.hidden, d))
        b2 = self.param("b2", nn.initializers.zeros, (e, d))
        h = nn.gelu(jnp.einsum("gnd,edf->gnef", x, w1) + b1)
        y = jnp.einsum("gnef,efd->gned", h, w2) + b2
        gate = jnp.max(probs, axis=-1)
        y = jnp.sum(y * taken[..., None], axis=2) * gate[..., None]
        return y.reshape(b, t, d)


class Model(nn.Module):
    vocab: int
    width: int
    heads: int
    layers: int
    hidden: int
    max_len: int
    experts: int = 0
    capacity_factor: float = 1.25
    aux_weight: float = 0.01

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        t = tokens.shape[1]
        x = nn.Embed(self.vocab, self.width, name="tok_embed")(tokens)
        x = x + nn.Embed(self.max_len, self.width,
                         name="pos_embed")(jnp.arange(t))[None]
        for i in range(self.layers):
            x = x + _Attention(self.heads, self.width,
                               name=f"attn_{i}")(nn.LayerNorm()(x))
            h = nn.LayerNorm()(x)
            if self.experts:
                h = _Switch(self.experts, self.width, self.hidden,
                            self.capacity_factor, self.aux_weight,
                            name=f"moe_{i}")(h, tokens != PAD)
            else:   # one after the other: flax numbers them as made
                h = nn.gelu(nn.Dense(self.hidden)(h))
                h = nn.Dense(self.width)(h)
            x = x + h
        return nn.Dense(self.vocab, name="lm_head")(nn.LayerNorm()(x))


def build_model(config: dict) -> nn.Module:
    m = config["model"]
    return Model(vocab=m["vocab"], width=m["d_model"], heads=m["n_heads"],
                 layers=m["n_layers"], hidden=m["d_ff"],
                 max_len=m["max_len"], experts=m["experts"],
                 capacity_factor=m["capacity_factor"],
                 aux_weight=m["aux_weight"])


def forward_macs_per_sample(config: dict, sample_shape) -> float:
    """Multiply-accumulates one window's forward pass requires: per token
    the four attention projections, its scores and mix against the whole
    window (as a dense causal attention computes them), the router, ONE
    expert's (or the MLP's) two products, and the vocabulary head.  The
    embeddings are lookups."""
    m = config["model"]
    (t,), d, f = sample_shape, m["d_model"], m["d_ff"]
    layer = 4 * d * d + 2 * t * d + 2 * d * f + d * m["experts"]
    return float(t * (m["n_layers"] * layer + d * m["vocab"]))
