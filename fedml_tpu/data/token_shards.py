"""Token shards: per-silo files of packed token-id sequences.

What a language-model fine-tuning job reads once its documents are
tokenized and packed: a directory with ``token_shards.json``::

    {"seq_len": 8192, "vocab": 19360, "shards": ["silo_00000.i32", ...]}

and one file a silo, little-endian int32, ``[n, seq_len + 1]`` row-major:
each row a packed sequence (documents one after another with an
end-of-document id between them, cut every ``seq_len + 1`` ids).  A row
gives ``x = row[:-1]`` and ``y = row[1:]``; id 0 is the pad id the
next-token loss leaves out, and a packed row holds none.  ``vocab`` is
the number of ids the rows are drawn from (a model's ``vocab_held``).
There is no test split: the round-0 evaluation is over the training rows.
"""

from __future__ import annotations

import json
import os

import numpy as np

from fedml_tpu.data.stacking import FederatedData, stack_client_data
from fedml_tpu.data.text import split_next_word

INDEX_FILE = "token_shards.json"


def load_token_shards(data_dir: str, batch_size: int = 1) -> FederatedData:
    with open(os.path.join(data_dir, INDEX_FILE)) as f:
        index = json.load(f)
    width = int(index["seq_len"]) + 1
    xs, ys = [], []
    for name in index["shards"]:
        rows = np.fromfile(os.path.join(data_dir, name),
                           dtype="<i4").reshape(-1, width)
        if rows.size and not (0 <= rows.min() and rows.max()
                              < index["vocab"]):
            raise ValueError(f"{name}: ids outside [0, {index['vocab']})")
        d = split_next_word(rows.astype(np.int32))
        xs.append(d["x"])
        ys.append(d["y"])
    return FederatedData(client_num=len(xs), class_num=int(index["vocab"]),
                         train=stack_client_data(xs, ys, batch_size))
