"""LSTM language models (parity: fedml_api/model/nlp/rnn.py:4-70).

Implemented with `flax.linen.RNN` over `OptimizedLSTMCell` — under jit the
recurrence compiles to a `lax.scan`, which XLA pipelines on TPU.  Zero
initial hidden state per batch, exactly as the reference notes
(rnn.py:26-29)."""

import flax.linen as nn
import jax.numpy as jnp


class RNNOriginalFedAvg(nn.Module):
    """Shakespeare next-char model (rnn.py:4-36): embed(8) -> 2x LSTM(256)
    -> dense(vocab) at EVERY position ([B, T, V] — the fed_shakespeare
    forward the reference keeps commented at rnn.py:33-35).  The data layer
    widens LEAF's single next-char label to the shifted sequence target
    (leaf.py load_shakespeare_leaf), so per-position logits are the
    framework-wide LM contract; McMahan'17's final-hidden prediction is
    logits[:, -1]."""
    vocab_size: int = 90
    embedding_dim: int = 8
    hidden_size: int = 256
    dtype: object = None    # bf16 mixed precision: compute dtype of every
                            # embed/LSTM/dense (params stay param_dtype f32)
    unroll: int = 1         # lax.scan unroll of the recurrence; >1 only for
                            # FLOPs accounting (XLA cost analysis counts a
                            # scan body once)

    @nn.compact
    def __call__(self, input_seq, train: bool = False):
        x = nn.Embed(self.vocab_size, self.embedding_dim,
                     dtype=self.dtype)(input_seq)
        x = nn.RNN(nn.OptimizedLSTMCell(self.hidden_size,
                                        dtype=self.dtype),
                   unroll=self.unroll)(x)
        x = nn.RNN(nn.OptimizedLSTMCell(self.hidden_size,
                                        dtype=self.dtype),
                   unroll=self.unroll)(x)
        return nn.Dense(self.vocab_size, dtype=self.dtype)(x)


class RNNStackOverflow(nn.Module):
    """StackOverflow next-word model (rnn.py:39-70): embed(96) -> LSTM(670)
    -> dense(96) -> dense(extended_vocab); per-position logits.

    Returns [B, T, V] (time-major logits transposed the torch way is [B, V, T];
    our loss consumes [B, T, V] directly)."""
    vocab_size: int = 10000
    num_oov_buckets: int = 1
    embedding_size: int = 96
    latent_size: int = 670
    num_layers: int = 1
    dtype: object = None    # bf16 mixed precision (see RNNOriginalFedAvg)

    @nn.compact
    def __call__(self, input_seq, train: bool = False):
        extended_vocab = self.vocab_size + 3 + self.num_oov_buckets
        x = nn.Embed(extended_vocab, self.embedding_size,
                     dtype=self.dtype)(input_seq)
        for _ in range(self.num_layers):
            x = nn.RNN(nn.OptimizedLSTMCell(self.latent_size,
                                            dtype=self.dtype))(x)
        x = nn.Dense(self.embedding_size, dtype=self.dtype)(x)
        return nn.Dense(extended_vocab, dtype=self.dtype)(x)
