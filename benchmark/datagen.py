"""Seeded data in the real on-disk formats, at the published scale.

The CLI's hermetic twin has ~32 samples a client and cannot be sized, so a
cell on it would time one batch per client.  Each generator here makes the
whole data set in memory from a seed (class prototypes mixed with noise, so
that a model learns on it and the loss moves within a few rounds), and each
writer puts it on disk in the layout the program's real loader reads.  The
plain reference takes the arrays straight from the generator: it never
reads what was written and shares nothing with ``fedml_tpu/``.

A configuration names its generator and writer as ``module:function`` in
its JSON file, so a later configuration brings its own beside its file.
"""

from __future__ import annotations

import os
import pickle

import numpy as np


def _noise_bytes(rng, shape) -> np.ndarray:
    """Uniform uint8 noise, drawn as raw bytes (the fastest draw numpy has:
    set-up is paid by every run of every later check)."""
    n = int(np.prod(shape))
    return np.frombuffer(rng.bytes(n), dtype=np.uint8).reshape(shape)


def femnist_arrays(seed: int, writers: int, min_train: int, max_train: int,
                   classes: int = 62) -> dict:
    """FEMNIST-shaped federated data: ``writers`` clients, train counts
    spread evenly over [min_train, max_train], test counts a ninth of
    that (at least 2), pixels float32 in [0, 1], labels in [0, classes)."""
    rng = np.random.default_rng([seed, 0xFE31])
    # the same counts for every seed, evenly spread over the range, dealt
    # to the writers in a seeded order: every seed has the same number of
    # rows, the same longest client (so the same padded step count and
    # compiled work) and cohorts of nearly the same size
    n_tr = min_train + (np.arange(writers) * (max_train - min_train)
                        // max(writers - 1, 1))
    n_tr = rng.permutation(n_tr)
    n_te = np.maximum(n_tr // 9, 2)
    proto = rng.random((classes, 28, 28), dtype=np.float32)

    def split(counts):
        total = int(counts.sum())
        y = rng.integers(0, classes, size=total).astype(np.int32)
        x = _noise_bytes(rng, (total, 28, 28)).astype(np.float32)
        x *= 0.5 / 255.0
        x += 0.5 * proto[y]
        return x, y, np.concatenate([[0], np.cumsum(counts)])

    x_tr, y_tr, off_tr = split(n_tr)
    x_te, y_te, off_te = split(n_te)
    return {"x_train": x_tr, "y_train": y_tr, "off_train": off_tr,
            "x_test": x_te, "y_test": y_te, "off_test": off_te}


def femnist_clients(arrays: dict, split: str = "train"):
    """Per-client (x [n,28,28,1], y [n]) in the order the loader reads
    them (h5 groups iterate by name; the writer names them in order)."""
    x, y, off = (arrays[f"x_{split}"], arrays[f"y_{split}"],
                 arrays[f"off_{split}"])
    return [(x[a:b, :, :, None], y[a:b]) for a, b in zip(off[:-1], off[1:])]


def write_femnist_h5(arrays: dict, out_dir: str) -> None:
    """``fed_emnist_{train,test}.h5`` in the TFF export's layout:
    ``examples/<writer>/{pixels [n,28,28] float32, label [n,1]}``."""
    import h5py
    os.makedirs(out_dir, exist_ok=True)
    for split in ("train", "test"):
        x, y, off = (arrays[f"x_{split}"], arrays[f"y_{split}"],
                     arrays[f"off_{split}"])
        path = os.path.join(out_dir, f"fed_emnist_{split}.h5")
        with h5py.File(path + ".tmp", "w") as f:
            ex = f.create_group("examples")
            for c, (a, b) in enumerate(zip(off[:-1], off[1:])):
                g = ex.create_group(f"f{c:05d}")
                g.create_dataset("pixels", data=x[a:b])
                g.create_dataset("label", data=y[a:b, None])
        os.replace(path + ".tmp", path)


def cifar10_arrays(seed: int, train: int = 50000, test: int = 10000,
                   classes: int = 10) -> dict:
    """CIFAR-10-shaped rows: uint8 [n, 3072] CHW-flat and labels, every
    class equally often (as in CIFAR-10), in a seeded order."""
    rng = np.random.default_rng([seed, 0xC1FA])
    proto = rng.integers(0, 256, size=(classes, 3072)).astype(np.float32)

    def split(n):
        y = rng.permutation(np.arange(n) % classes).astype(np.int64)
        x = _noise_bytes(rng, (n, 3072)).astype(np.float32)
        x += proto[y]
        x *= 0.5
        x = x.astype(np.uint8)
        return x, y

    x_tr, y_tr = split(train)
    x_te, y_te = split(test)
    return {"x_train": x_tr, "y_train": y_tr, "x_test": x_te, "y_test": y_te}


def write_cifar10_pickles(arrays: dict, out_dir: str) -> None:
    """``cifar-10-batches-py/`` as the CIFAR-10 python archive unpacks:
    ``data_batch_1..5`` and ``test_batch``, each a pickled dict with
    ``data`` [n, 3072] uint8 and ``labels`` (a list)."""
    root = os.path.join(out_dir, "cifar-10-batches-py")
    os.makedirs(root, exist_ok=True)
    parts = np.array_split(np.arange(len(arrays["y_train"])), 5)
    files = [(f"data_batch_{i + 1}", arrays["x_train"][p],
              arrays["y_train"][p]) for i, p in enumerate(parts)]
    files.append(("test_batch", arrays["x_test"], arrays["y_test"]))
    for name, x, y in files:
        path = os.path.join(root, name)
        with open(path + ".tmp", "wb") as f:
            pickle.dump({"data": x, "labels": [int(v) for v in y]}, f,
                        protocol=pickle.HIGHEST_PROTOCOL)
        os.replace(path + ".tmp", path)


def cifar_hwc01(flat: np.ndarray) -> np.ndarray:
    """uint8 CHW-flat rows -> float32 HWC in [0, 1] (what any CIFAR
    pipeline feeds a model)."""
    return (flat.reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
            .astype(np.float32) / 255.0)


def lda_partition(labels: np.ndarray, clients: int, classes: int,
                  alpha: float, seed: int, floor: int = 10):
    """The latent-Dirichlet split FedML's CIFAR benchmarks publish (Hsu et
    al. 2019; FedML ``noniid_partition.py``), written from that
    description: class by class, shuffle the class's rows, draw client
    proportions from Dir(alpha), give nothing more to a client that
    already holds its even share, cut; redo the whole draw while some
    client holds fewer than ``floor`` rows; shuffle each client's rows."""
    rng = np.random.RandomState(seed)
    n = len(labels)
    smallest = 0
    while smallest < floor:
        held = [[] for _ in range(clients)]
        for k in range(classes):
            rows = np.where(labels == k)[0]
            rng.shuffle(rows)
            p = rng.dirichlet(np.repeat(alpha, clients))
            p = np.array([q * (len(h) < n / clients)
                          for q, h in zip(p, held)])
            p = p / p.sum()
            cuts = (np.cumsum(p) * len(rows)).astype(int)[:-1]
            held = [h + part.tolist()
                    for h, part in zip(held, np.split(rows, cuts))]
            smallest = min(len(h) for h in held)
    out = []
    for h in held:
        rng.shuffle(h)
        out.append(np.asarray(h, dtype=np.int64))
    return out


def cifar10_clients(arrays: dict, clients: int, alpha: float,
                    partition_seed: int):
    """Per-silo training rows (x [n,32,32,3] float32, y [n]) under the
    LDA split."""
    x = cifar_hwc01(arrays["x_train"])
    y = arrays["y_train"].astype(np.int32)
    parts = lda_partition(arrays["y_train"], clients, 10, alpha,
                          partition_seed)
    return [(x[p], y[p]) for p in parts]
