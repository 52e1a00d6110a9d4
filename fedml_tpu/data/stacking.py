"""Host-side cohort staging: ragged per-client data -> padded device arrays.

The reference feeds each client a torch DataLoader over its own tensor list
(MNIST/data_loader.py:51-75) and the simulator re-points one trainer at a
different client's loader each round (FedAVGTrainer.update_dataset,
FedAVGTrainer.py:25-29).  The TPU equivalent (SURVEY.md §2.4): keep ALL
clients' data in stacked host arrays ``[num_clients, S, B, ...]`` padded to
a common S, and per round *gather* the sampled cohort's rows and ship one
contiguous block to device.  Masks keep padded rows out of loss/metrics, so
sample-weighted aggregation stays exact despite padding.

This is the "process k plays client i" trick turned into an indexed gather —
no per-round re-staging, no re-jit (cohort shapes are static).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence

import numpy as np
import jax.numpy as jnp

from fedml_tpu.obs import trace

Array = np.ndarray


@dataclasses.dataclass
class FederatedData:
    """The uniform dataset contract (TPU-native version of the reference's
    9-tuple, e.g. main_fedavg.py:118-120).

    train: dict of stacked arrays {x: [N, S, B, ...], y: [N, S, B, ...],
           mask: [N, S, B], num_samples: [N]} over all N clients.
    test/global test: same layout (or None).
    """
    client_num: int
    class_num: int
    train: Dict[str, Array]
    test: Optional[Dict[str, Array]] = None
    train_global: Optional[Dict[str, Array]] = None
    test_global: Optional[Dict[str, Array]] = None
    # (start, duration) of the load on perf_counter_ns, where the loader's
    # caller stamped it: a runner records the span ``setup.data`` from it
    load_ns: Optional[tuple] = None

    @property
    def train_data_num(self) -> int:
        return int(self.train["num_samples"].sum())


def stack_client_data(xs: Sequence[Array], ys: Sequence[Array],
                      batch_size: int, steps: Optional[int] = None,
                      shuffle_seed: Optional[int] = None) -> Dict[str, Array]:
    """Stack ragged per-client (x, y) into [C, S, B, ...] + mask + counts.

    S = ceil(max_i n_i / B) unless given.  Clients with fewer samples get
    zero-padded batches with mask 0.  With ``shuffle_seed`` each client's
    samples are shuffled once (the reference shuffles MNIST with fixed seed
    100, MNIST/data_loader.py:51-56)."""
    C = len(xs)
    assert C == len(ys)
    rng = np.random.RandomState(shuffle_seed) if shuffle_seed is not None else None
    counts = np.asarray([len(x) for x in xs], dtype=np.int64)
    if steps is None:
        steps = int(np.ceil(max(int(counts.max()), 1) / batch_size))
    cap = steps * batch_size

    # derive shapes/dtypes from the first NON-empty client, so absent users
    # (LEAF splits missing a user yield shape-(0,) arrays) don't poison the
    # stacked layout
    x0 = next((np.asarray(x) for x in xs if len(x)), np.asarray(xs[0]))
    sample_shape = x0.shape[1:]
    x_out = np.zeros((C, steps, batch_size) + sample_shape, dtype=x0.dtype)
    y0 = next((np.asarray(y) for y in ys if len(y)), np.asarray(ys[0]))
    y_shape = y0.shape[1:]
    y_dtype = y0.dtype
    y_out = np.zeros((C, steps, batch_size) + y_shape, dtype=y_dtype)
    mask = np.zeros((C, steps, batch_size), dtype=np.float32)

    clipped = np.minimum(counts, cap)
    for c in range(C):
        n = int(clipped[c])
        if n == 0:  # empty client: all-zero padding, mask 0, weight 0
            continue
        x = np.asarray(xs[c])[:n]
        y = np.asarray(ys[c])[:n]
        if rng is not None and n > 1:
            perm = rng.permutation(n)
            x, y = x[perm], y[perm]
        flat_x = x_out[c].reshape((cap,) + sample_shape)
        flat_y = y_out[c].reshape((cap,) + y_shape)
        flat_m = mask[c].reshape(cap)
        flat_x[:n] = x
        flat_y[:n] = y
        flat_m[:n] = 1.0
    return {"x": x_out, "y": y_out, "mask": mask,
            "num_samples": clipped.astype(np.float32)}


def batch_global(x: Array, y: Array, batch_size: int) -> Dict[str, Array]:
    """Batch one (global) dataset into [S, B, ...] + mask (for centralized
    training / server-side eval)."""
    d = stack_client_data([x], [y], batch_size)
    return {"x": d["x"][0], "y": d["y"][0], "mask": d["mask"][0]}


def save_stacked(stacked: Dict[str, Array], out_dir: str) -> None:
    """Persist a stacked client tree as one ``.npy`` per key (the staging
    format for corpora that exceed RAM — see load_stacked_memmap)."""
    import os
    os.makedirs(out_dir, exist_ok=True)
    for k, v in stacked.items():
        np.save(os.path.join(out_dir, f"{k}.npy"), np.asarray(v))


def load_stacked_memmap(in_dir: str) -> Dict[str, Array]:
    """Load a saved stacked tree memory-mapped (SURVEY.md §7 hard part (f):
    342k-client StackOverflow without re-staging).

    The [N, S, B, ...] arrays stay on disk; ``gather_cohort``'s fancy-index
    ``v[ids]`` copies ONLY the sampled cohort's rows per round, so host RAM
    holds one cohort, not the corpus.  FedAvg's HBM budget check reads
    ``nbytes`` without materialising, so an over-budget memmap dataset
    automatically stays on the per-round host-gather path."""
    import os
    out = {}
    for f in sorted(os.listdir(in_dir)):
        if f.endswith(".npy"):
            out[f[:-4]] = np.load(os.path.join(in_dir, f), mmap_mode="r")
    return out


def gather_cohort(stacked: Dict[str, Array], client_ids: Sequence[int],
                  pad_to: Optional[int] = None) -> Dict[str, Any]:
    """Select the sampled cohort's rows; optionally pad with weight-0 dummy
    clients to a static cohort size (kills per-round re-jit, SURVEY.md §7
    "hard parts" (a)).

    The padded-slot contract, which the static-wave cross-device path
    makes the COMMON case rather than the edge case (pinned in
    tests/test_cross_device.py): a padded slot aliases client 0's rows
    but carries ``mask 0`` and ``num_samples 0``, so the local trainer
    leaves its params at the round global (every batch holds no row:
    each step is skipped where the clients train in sequence, computed
    and thrown away under ``vmap``; `make_local_trainer`)
    and any weighted reduction sees an exact ``+0.0`` — a wave of ALL
    pad slots therefore folds as weight 0, never a 0/0 normalizer.  A
    cohort LARGER than ``pad_to`` is a caller bug (the jit downstream
    would silently retrace on the odd-sized stack) and fails loudly.

    Threads and ownership: ``stacked`` is only read, so any thread may
    call this beside any other (the cross-device engine calls it on its
    staging worker, one wave ahead of the loop, and inline on a miss).
    Every call gathers into fresh host arrays that belong to the
    returned device arrays alone: ``jnp.asarray`` may return before the
    copy is done (TPU) or alias the numpy memory (CPU), so nothing here
    keeps, reuses or writes them afterwards, and a caller must not
    either.  The two spans open under whatever site is open on the
    calling thread (`trace.child`)."""
    ids = np.asarray(client_ids, dtype=np.int64)
    if pad_to is not None and len(ids) > pad_to:
        raise ValueError(
            f"gather_cohort: {len(ids)} sampled clients exceed "
            f"pad_to={pad_to}; the static cohort shape cannot hold them "
            f"(chunk the cohort — device_cohort.plan_waves — or raise "
            f"pad_to)")
    n_live = len(ids)
    if pad_to is not None and n_live < pad_to:
        ids = np.concatenate([ids, np.zeros(pad_to - n_live, np.int64)])
    live = (np.arange(len(ids)) < n_live).astype(np.float32)
    # two spans under the caller's (the round's ``wave``, or the staging
    # worker's ``stage.prefetch``), where one is open: the numpy row
    # gather, then the hand-over to the device
    with trace.child("stage.gather") as sp:
        rows = {k: v[ids] for k, v in stacked.items()}
        if sp is not None:
            # counts made where the rows are: what is gathered here is
            # what ``stage.put`` hands over
            sp.set(bytes=sum(int(v.nbytes) for v in rows.values()),
                   rows_padded=int(rows["mask"].size),
                   rows_real=int(rows["num_samples"][:n_live].sum()))
    with trace.child("stage.put"):
        out = {k: jnp.asarray(v) for k, v in rows.items()}
        out["mask"] = out["mask"] * jnp.asarray(live)[:, None, None]
        out["num_samples"] = out["num_samples"] * jnp.asarray(live)
    return out
