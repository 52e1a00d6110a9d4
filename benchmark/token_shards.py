"""Seeded token shards for a language-model cell: the generator, and the
writer of the on-disk layout the program's ``token_shards`` loader reads
(``token_shards.json`` + one little-endian int32 file ``[n, T + 1]`` a
silo; stated here from the format, nothing of ``fedml_tpu`` is imported).

A silo's documents have log-normal lengths (heavy-tailed, as real corpora
are) and Zipf(``zipf``) token ids over the ids the model's slice of the
vocabulary holds; they are packed one after another, each closed by the
end-of-document id, and cut into rows of ``seq_len + 1`` ids (a row gives
``x = row[:-1]``, ``y = row[1:]``).  Id 0 is the pad id the next-token
loss leaves out and is never drawn; id 1 closes a document; the ids from 2
on are words, the most frequent first in a seeded order of its own per
seed.  Every seed has the same number of rows a silo, so the same work.
"""

from __future__ import annotations

import json
import os

import numpy as np

PAD, EOD, FIRST_WORD = 0, 1, 2


def token_shard_arrays(seed: int, silos: int, sequences: int, seq_len: int,
                       vocab: int, zipf: float = 1.0,
                       doc_median: int = 600, doc_sigma: float = 1.0) -> dict:
    """``{"train": [int32 [sequences, seq_len + 1] per silo], "vocab":
    vocab}``."""
    rng = np.random.default_rng([seed, 0x70C5])
    words = vocab - FIRST_WORD
    p = 1.0 / np.arange(1, words + 1, dtype=np.float64) ** zipf
    cdf = np.cumsum(p / p.sum())
    by_rank = FIRST_WORD + rng.permutation(words)
    need = sequences * (seq_len + 1)
    out = []
    for _ in range(silos):
        ids = by_rank[np.minimum(np.searchsorted(cdf, rng.random(need)),
                                 words - 1)].astype(np.int32)
        # close a document wherever its drawn length ends
        at = 0
        while at < need:
            at += max(int(rng.lognormal(np.log(doc_median), doc_sigma)), 2)
            if at < need:
                ids[at] = EOD
                at += 1
        out.append(ids.reshape(sequences, seq_len + 1))
    return {"train": out, "vocab": vocab}


def write_token_shards(arrays: dict, out_dir: str) -> None:
    os.makedirs(out_dir, exist_ok=True)
    names = []
    for i, rows in enumerate(arrays["train"]):
        names.append(f"silo_{i:05d}.i32")
        path = os.path.join(out_dir, names[-1])
        np.ascontiguousarray(rows, dtype="<i4").tofile(path + ".tmp")
        os.replace(path + ".tmp", path)
    with open(os.path.join(out_dir, "token_shards.json"), "w") as f:
        json.dump({"seq_len": int(arrays["train"][0].shape[1]) - 1,
                   "vocab": int(arrays["vocab"]), "shards": names}, f)
