"""Decentralized (serverless) FL — gossip over a topology.

Parity with the reference's two decentralized stacks:

* distributed demo (fedml_api/distributed/decentralized_framework/
  decentralized_worker_manager.py:29-46): every worker trains, pushes its
  result to its topology out-neighbors, and finishes the round when all
  in-neighbors arrived;
* the topology-weighted mixing itself comes from
  fedml_core/distributed/topology (row-stochastic matrices).

TPU-native execution (SURVEY.md §3.5): node states live stacked on a
``nodes`` axis and one gossip round is

    W @ stacked_params        (dense mixing, single chip), or
    `lax.ppermute` neighbor exchange over the mesh (ring),

both inside the same jit as the per-node local training — the message
choreography disappears entirely.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from fedml_tpu.core.topology import SymmetricTopologyManager
from fedml_tpu.data.stacking import FederatedData
from fedml_tpu.parallel.cohort import cohort_eval
from fedml_tpu.trainer.local_sgd import make_local_trainer, make_evaluator
from fedml_tpu.trainer.workload import Workload, make_client_optimizer

logger = logging.getLogger(__name__)
Pytree = Any


@dataclasses.dataclass
class DecentralizedConfig:
    comm_round: int = 10
    epochs: int = 1
    batch_size: int = 10
    lr: float = 0.03
    client_optimizer: str = "sgd"
    wd: float = 0.0
    neighbor_num: int = 2
    frequency_of_the_test: int = 5
    seed: int = 0


def mix_stacked(stacked: Pytree, W: jax.Array) -> Pytree:
    """One gossip mixing step: row-stochastic W applied along the node axis.
    Runs on the MXU as a [N,N]x[N,D] matmul per leaf."""
    def _mix(x):
        flat = x.reshape(x.shape[0], -1)
        mixed = (W.astype(jnp.float32) @ flat.astype(jnp.float32))
        return mixed.reshape(x.shape).astype(x.dtype)
    return jax.tree.map(_mix, stacked)


def ring_mix_sharded(local: Pytree, axis_name: str, w_self: float,
                     w_left: float, w_right: float) -> Pytree:
    """Ring gossip over a mesh axis with two `ppermute`s — the ICI-native
    neighbor exchange (one node per device)."""
    n = jax.lax.axis_size(axis_name)
    if not isinstance(n, int):
        # the traced psum-of-ones last resort serves arithmetic-only
        # callers (hierarchical's copy divisor); the ppermute tables
        # below need a CONCRETE size — name the requirement instead of
        # letting range(tracer) die deep inside tracing
        raise RuntimeError(
            "ring_mix_sharded needs a STATIC mesh-axis size to build "
            "its ppermute tables, and this jax exposes neither "
            "jax.lax.axis_size nor the axis-env probe; upgrade jax "
            "(the dense mix_stacked path works everywhere)")
    perm_fwd = [(i, (i + 1) % n) for i in range(n)]
    perm_bwd = [(i, (i - 1) % n) for i in range(n)]

    def _mix(x):
        from_left = jax.lax.ppermute(x, axis_name, perm_fwd)
        from_right = jax.lax.ppermute(x, axis_name, perm_bwd)
        return w_self * x + w_left * from_left + w_right * from_right
    return jax.tree.map(_mix, local)


def _ring_weights(W: np.ndarray):
    """Validate that W is a circulant ring mixing matrix (nonzero only on the
    diagonal and the two ring neighbors, uniform across rows) and return
    (w_self, w_left, w_right).  The ppermute mesh path supports exactly this
    structure; other topologies need the dense path."""
    n = W.shape[0]
    if n == 1:
        if not np.allclose(W, 1.0, atol=1e-6):
            raise ValueError("1-node gossip requires W == [[1.0]]")
        return 1.0, 0.0, 0.0
    if n == 2:
        # both ring directions alias the single neighbor, so its weight is
        # split between the two ppermute arrivals (their sum is what mixes)
        expect = np.array([[W[0, 0], W[0, 1]], [W[0, 1], W[0, 0]]])
        if not np.allclose(W, expect, atol=1e-6):
            raise ValueError("2-node gossip requires a symmetric circulant W")
        return float(W[0, 0]), float(W[0, 1]) / 2, float(W[0, 1]) / 2
    ring = np.zeros_like(W)
    for i in range(n):
        ring[i, i] = W[0, 0]
        ring[i, (i - 1) % n] = W[0, n - 1]
        ring[i, (i + 1) % n] = W[0, 1]
    if not np.allclose(W, ring, atol=1e-6):
        raise ValueError(
            "mesh gossip supports ring topologies only (nonzeros on the "
            "diagonal and adjacent ring neighbors); use the dense path "
            "(mesh=None) for general mixing matrices")
    return float(W[0, 0]), float(W[0, n - 1]), float(W[0, 1])


class DecentralizedGossip:
    """All-node local training + topology mixing, one jit per round."""

    def __init__(self, workload: Workload, data: FederatedData,
                 config: DecentralizedConfig, mesh=None,
                 topology: Optional[np.ndarray] = None):
        self.workload = workload
        self.data = data
        self.cfg = config
        n = data.client_num
        if topology is None:
            mgr = SymmetricTopologyManager(n, config.neighbor_num)
            topology = mgr.generate_topology()
        self.W = jnp.asarray(topology, jnp.float32)

        opt = make_client_optimizer(config.client_optimizer, config.lr,
                                    config.wd)
        local_train = make_local_trainer(workload, opt, config.epochs)
        self.evaluate = make_evaluator(workload)
        self._eval_cohort = cohort_eval(self.evaluate)
        self.history = []

        if mesh is None:
            @jax.jit
            def round_fn(stacked_params, data_stacked, rng, W):
                nloc = data_stacked["num_samples"].shape[0]
                rngs = jax.vmap(
                    lambda i: jax.random.fold_in(rng, i))(jnp.arange(nloc))
                batches = {k: v for k, v in data_stacked.items()
                           if k != "num_samples"}
                trained, _ = jax.vmap(local_train)(stacked_params, batches, rngs)
                return mix_stacked(trained, W)
            self._round = lambda s, d, r: round_fn(s, d, r, self.W)
        else:
            if n != mesh.shape["clients"]:
                raise ValueError("mesh gossip needs one node per device")
            w_self, w_left, w_right = _ring_weights(np.asarray(self.W))

            def per_device(stacked_params, data_stacked, rng):
                rng = jax.lax.pcast(rng, ("clients",), to="varying")
                i = jax.lax.axis_index("clients")
                local_params = jax.tree.map(lambda x: x[0], stacked_params)
                local_data = jax.tree.map(lambda x: x[0], data_stacked)
                r = jax.random.fold_in(rng, i)
                batches = {k: v for k, v in local_data.items()
                           if k != "num_samples"}
                trained, _ = local_train(local_params, batches, r)
                mixed = ring_mix_sharded(trained, "clients",
                                         w_self, w_left, w_right)
                return jax.tree.map(lambda x: x[None], mixed)

            self._round = jax.jit(jax.shard_map(
                per_device, mesh=mesh,
                in_specs=(P("clients"), P("clients"), P()),
                out_specs=P("clients")))

    def run(self, stacked_params=None, rng=None):
        cfg = self.cfg
        rng = rng if rng is not None else jax.random.key(cfg.seed)
        train = {k: jnp.asarray(v) for k, v in self.data.train.items()}
        if stacked_params is None:
            rng, init_rng = jax.random.split(rng)
            p0 = self.workload.init(init_rng, jax.tree.map(
                lambda v: v[0, 0], {k: train[k] for k in ("x", "y", "mask")}))
            stacked_params = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (self.data.client_num,) + x.shape),
                p0)

        for r in range(cfg.comm_round):
            rng, rr = jax.random.split(rng)
            stacked_params = self._round(stacked_params, train, rr)
            if r % cfg.frequency_of_the_test == 0 or r == cfg.comm_round - 1:
                # consensus check + node-0 model quality
                p0 = jax.tree.map(lambda x: x[0], stacked_params)
                m = self._eval_cohort(p0, train)
                acc = float(m["correct"]) / max(float(m["total"]), 1.0)
                self.history.append({"round": r, "train_acc": acc})
                logger.info("gossip round %d acc %.4f", r, acc)
        return stacked_params
