"""Federated long-context training: dp × sp in ONE compiled program.

The reference caps sequences at one process's memory (its largest NLP model
is a 2-layer LSTM on 80-token windows, fedml_api/model/nlp/rnn.py:18-22;
SURVEY.md §5.7).  Here a cohort trains over a 2-D ``[clients, sequence]``
mesh: the cohort is data-parallel over the ``clients`` axis exactly as in
the cohort engine (fedml_tpu/parallel/cohort.py), while INSIDE each client's
local SGD the transformer's sequence axis is sharded over ``sequence`` with
exact ring attention (fedml_tpu/parallel/ring_attention.py).  One shard_map,
two collectives families: ring `ppermute` + loss/grad `psum` over
``sequence`` within a client, weighted aggregation `psum` over ``clients``
across the cohort.

SPMD correctness notes (the two easy-to-get-wrong pieces):

* the per-position CE is normalized by GLOBAL psum'd counts, so every
  sequence shard computes the identical loss value;
* each shard's backward produces only its PARTIAL gradient (its own logits'
  contribution), so the local trainer psums gradients over ``sequence``
  before the optimizer step (``grad_reduce`` hook, trainer/local_sgd.py) —
  all shards then take identical optimizer steps and parameters stay in
  sync without any explicit broadcast.

Parity test: dp×sp on the 8-device mesh == single-chip vmap cohort with
dense attention (tests/test_ring_attention.py).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, PartitionSpec as P

from fedml_tpu.parallel.cohort import train_cohort
from fedml_tpu.trainer.local_sgd import make_local_trainer
from fedml_tpu.trainer.workload import Workload


def make_sp_nwp_workload(model, axis_name: str = "sequence",
                         pad_id: int = 0,
                         grad_clip_norm: Optional[float] = None) -> Workload:
    """Next-token workload over a sequence-sharded model.

    ``model`` is a TransformerLM (anything taking ``positions``/
    ``ring_axis``).  ``loss_fn`` runs INSIDE a shard_map over ``axis_name``:
    the batch's token dim is the local shard, global positions come from the
    mesh coordinate, and sums/counts psum over the axis so the loss (and
    therefore the optimizer trajectory) is identical on every shard.

    ``init`` runs dense (outside the mesh) — fine for initialization since
    no [T, T] scores materialize there; at truly init-bound lengths,
    initialize at a shorter T (parameters are length-independent).

    Dropout caveat: per-shard dropout rngs would decorrelate across the
    sequence axis; keep ``dropout_rate=0`` for sp runs (the default).
    """

    def _position_mask(batch):
        tok_valid = (batch["y"] != pad_id).astype(jnp.float32)
        return tok_valid * batch["mask"][:, None]

    def _logits(params, batch, train):
        t_local = batch["x"].shape[-1]
        pos = (jax.lax.axis_index(axis_name) * t_local
               + jnp.arange(t_local))
        out = model.apply({"params": params}, batch["x"], train=train,
                          positions=pos, ring_axis=axis_name)
        return out.astype(jnp.float32)

    def loss_fn(params, batch, rng, train):
        logits = _logits(params, batch, train)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits,
                                                             batch["y"])
        m = _position_mask(batch)
        total = jax.lax.psum(jnp.sum(ce * m), axis_name)
        count = jax.lax.psum(jnp.sum(m), axis_name)
        loss = total / jnp.maximum(count, 1.0)
        return loss, {"loss": loss}

    def metric_fn(params, batch):
        logits = _logits(params, batch, train=False)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits,
                                                             batch["y"])
        pred = jnp.argmax(logits, axis=-1)
        m = _position_mask(batch)
        return {
            "correct": jax.lax.psum(jnp.sum((pred == batch["y"]) * m),
                                    axis_name),
            "loss_sum": jax.lax.psum(jnp.sum(ce * m), axis_name),
            "total": jax.lax.psum(jnp.sum(m), axis_name),
        }

    return Workload(model=model, loss_fn=loss_fn, metric_fn=metric_fn,
                    grad_clip_norm=grad_clip_norm)


def make_sp_mesh(n_clients: int, n_sequence: int, devices=None) -> Mesh:
    """[clients, sequence] grid.  Lay devices so the sequence axis (the
    latency-critical ring) rides contiguous ICI neighbors."""
    import numpy as np
    devs = list(devices if devices is not None else jax.devices())
    if n_clients * n_sequence != len(devs):
        raise ValueError(f"mesh {n_clients}x{n_sequence} != "
                         f"{len(devs)} devices")
    return Mesh(np.asarray(devs).reshape(n_clients, n_sequence),
                ("clients", "sequence"))


def make_sp_cohort_step(workload: Workload,
                        optimizer: optax.GradientTransformation,
                        epochs: int, mesh: Mesh,
                        axis_name: str = "sequence"):
    """One federated round over the [clients, sequence] mesh.

    ``step(params, cohort_data, rng) -> (new_params, metrics)``; cohort
    leaves [C, S, B, ...] with the token dim of x/y sharded over
    ``axis_name`` and clients over ``clients``.  The aggregation psums over
    BOTH axes with the sequence copies divided out, which also proves the
    fully-replicated out_spec (same trick as the two-level hierarchical
    mesh, algorithms/hierarchical.py).
    """
    local_train = make_local_trainer(
        workload, optimizer, epochs,
        grad_reduce=lambda g: jax.lax.psum(g, axis_name))
    n_cli = mesh.shape["clients"]
    n_seq = mesh.shape[axis_name]

    def _sharded(params, data, rng):
        params = jax.lax.pcast(params, ("clients", axis_name),
                               to="varying")
        rng = jax.lax.pcast(rng, ("clients", axis_name), to="varying")
        local_c = data["num_samples"].shape[0]
        offset = jax.lax.axis_index("clients") * local_c
        stacked, metrics = train_cohort(local_train, params, data, rng,
                                        index_offset=offset)
        w = data["num_samples"].astype(jnp.float32)
        total = jax.lax.psum(jnp.sum(w), "clients")
        ratio = w / jnp.maximum(total, 1.0) / n_seq
        new_global = jax.tree.map(
            lambda x: jax.lax.psum(jnp.sum(
                x.astype(jnp.float32)
                * ratio.reshape((-1,) + (1,) * (x.ndim - 1)), axis=0),
                ("clients", axis_name)).astype(x.dtype),
            stacked)
        # per-step losses are already psum'd over the sequence axis inside
        # the loss, so divide out nothing — just prove invariance
        metrics = jax.tree.map(
            lambda x: jax.lax.psum(x, axis_name) / n_seq, metrics)
        return new_global, metrics

    data_spec = {"x": P("clients", None, None, axis_name),
                 "y": P("clients", None, None, axis_name),
                 "mask": P("clients"),
                 "num_samples": P("clients")}
    sharded = jax.shard_map(_sharded, mesh=mesh,
                            in_specs=(P(), data_spec, P()),
                            out_specs=(P(), P("clients")))

    @jax.jit
    def step(params, cohort_data, rng):
        C = cohort_data["num_samples"].shape[0]
        T = cohort_data["x"].shape[-1]
        if C % n_cli:
            raise ValueError(f"cohort size {C} not divisible by the mesh "
                             f"clients axis ({n_cli})")
        if T % n_seq:
            raise ValueError(f"sequence length {T} not divisible by the "
                             f"mesh sequence axis ({n_seq})")
        return sharded(params, cohort_data, rng)

    return step
