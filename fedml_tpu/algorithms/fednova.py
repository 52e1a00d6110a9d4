"""FedNova (Wang et al. 2020) — normalized averaging of heterogeneous
local updates.

Parity with fedml_api/standalone/fednova/:

* the client optimizer (fednova.py:109-155): SGD with weight decay, heavy-
  ball momentum (optionally nesterov), FedProx mu term, an accumulated
  update ``cum_grad += lr * d_p``, and the normalizing scalar a_i
  (``local_normalizing_vec``, :141-149) whose update rule depends on
  momentum/mu exactly as in the reference;
* aggregation (fednova_trainer.py:97-115 + fednova.py:155-185):
  tau_eff = Σ_i p_i·a_i (or p_i·steps_i when mu≠0), each client contributes
  p_i·cum_grad_i/a_i, the server applies w ← w − tau_eff·Σ_i contribution,
  with optional server "global momentum" gmf (buf = gmf·buf + cum_grad/lr;
  w ← w − lr·buf).

The reference runs this over torch.distributed all_reduce helpers
(comm_helpers.py:48-60) — a second comm stack beside MPI.  Here both the
per-client loop and the aggregation are one jit: the client scan carries
(params, momentum buffer, cum_grad, a_i) and aggregation is a weighted
reduction over the stacked client axis.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from fedml_tpu.algorithms.fedavg import FedAvg, FedAvgConfig
from fedml_tpu.parallel.cohort import train_cohort

Pytree = Any


@dataclasses.dataclass
class FedNovaConfig(FedAvgConfig):
    momentum: float = 0.0
    nesterov: bool = False
    mu: float = 0.0          # FedProx term inside the Nova optimizer
    gmf: float = 0.0         # global (server) momentum factor


def make_fednova_local_trainer(workload, cfg: FedNovaConfig):
    """Returns train(params, data, rng) -> (new_params, aux) where aux carries
    cum_grad (pytree), a_i, local_steps."""
    lr, m, mu = cfg.lr, cfg.momentum, cfg.mu
    nesterov = cfg.nesterov
    wd = cfg.wd

    grad_fn = jax.grad(lambda p, b, r: workload.loss_fn(p, b, r, True)[0])

    def train(params: Pytree, data: Dict[str, jax.Array], rng: jax.Array):
        init_params = params
        zeros = jax.tree.map(jnp.zeros_like, params)
        num_steps = jax.tree.leaves(data)[0].shape[0]

        def step(carry, step_idx):
            params, buf, cum_grad, counter, a_i, rng = carry
            rng, drng = jax.random.split(rng)
            batch = jax.tree.map(lambda x: x[step_idx % num_steps], data)
            grads = grad_fn(params, batch, drng)
            got_data = jnp.sum(batch["mask"]) > 0
            if wd:
                grads = jax.tree.map(lambda g, p: g + wd * p, grads, params)
            if m:
                # torch sgd momentum with the reference's first-step
                # initialization buf=d_p: emulate by buf_new = m*buf + d_p
                # with buf starting at 0 (identical sequence for dampening=0);
                # frozen on fully-padded batches like every other carry
                buf = jax.tree.map(
                    lambda b, g: jnp.where(got_data, m * b + g, b), buf, grads)
                if nesterov:
                    d_p = jax.tree.map(lambda g, b: g + m * b, grads, buf)
                else:
                    d_p = buf
            else:
                d_p = grads
            if mu:
                d_p = jax.tree.map(lambda d, p, p0: d + mu * (p - p0),
                                   d_p, params, init_params)
            gd = got_data.astype(jnp.float32)
            cum_grad = jax.tree.map(lambda c, d: c + lr * d * gd, cum_grad, d_p)
            params = jax.tree.map(lambda p, d: p - lr * d * gd, params, d_p)

            # a_i bookkeeping (fednova.py:141-149), frozen on padded steps
            if m:
                counter = jnp.where(got_data, counter * m + 1.0, counter)
                a_i = jnp.where(got_data, a_i + counter, a_i)
            etamu = lr * mu
            if etamu:
                a_i = jnp.where(got_data, a_i * (1 - etamu) + 1.0, a_i)
            if not m and not etamu:
                a_i = jnp.where(got_data, a_i + 1.0, a_i)
            return (params, buf, cum_grad, counter, a_i, rng), None

        total = cfg.epochs * num_steps
        carry = (params, zeros, zeros, jnp.float32(0), jnp.float32(0), rng)
        (params, _, cum_grad, _, a_i, _), _ = jax.lax.scan(
            step, carry, jnp.arange(total))
        steps_taken = jnp.sum(
            (jnp.sum(data["mask"], axis=tuple(range(1, data["mask"].ndim))) > 0)
            .astype(jnp.float32)) * cfg.epochs
        return params, {"cum_grad": cum_grad, "a_i": a_i,
                        "local_steps": steps_taken}

    return train


class FedNova(FedAvg):
    def __init__(self, workload, data, config: FedNovaConfig, mesh=None, sink=None):
        super().__init__(workload, data, config, mesh=mesh, sink=sink)
        cfg = config
        if cfg.client_axis not in (None, "vmap"):
            # the Nova round has its own train_cohort call sites; a
            # silently-vmapped "scan" request would mislabel the engine
            raise ValueError("client_axis is not wired into FedNova's "
                             "custom round; leave it None")
        local_train = make_fednova_local_trainer(workload, cfg)
        self._gmf_buf = None

        def _nova_core(global_params, cohort_data, rng, gmf_buf, psum_axis,
                       index_offset=0):
            """Shared single-chip / per-shard body.  With psum_axis set, the
            partial sums ride ICI and every device ends with the global
            update (the same two-psum pattern as tree_weighted_psum_mean)."""
            n = cohort_data["num_samples"].astype(jnp.float32)
            _, aux = train_cohort(local_train, global_params, cohort_data,
                                  rng, index_offset=index_offset,
                                  client_axis="vmap")

            total = jnp.sum(n)
            if psum_axis:
                total = jax.lax.psum(total, psum_axis)
            ratio = n / jnp.maximum(total, 1.0)
            a = jnp.maximum(aux["a_i"], 1e-12)
            tau_src = aux["local_steps"] if cfg.mu != 0 else aux["a_i"]
            tau_eff = jnp.sum(ratio * tau_src)
            if psum_axis:
                tau_eff = jax.lax.psum(tau_eff, psum_axis)

            def _nova_sum(cg):  # Σ_i p_i/a_i · cum_grad_i, then · tau_eff
                w = (ratio / a).reshape((-1,) + (1,) * (cg.ndim - 1))
                part = jnp.sum(cg * w, axis=0)
                if psum_axis:
                    part = jax.lax.psum(part, psum_axis)
                return tau_eff * part

            cum = jax.tree.map(_nova_sum, aux["cum_grad"])
            if cfg.gmf:
                gmf_buf = jax.tree.map(
                    lambda b, c: cfg.gmf * b + c / cfg.lr, gmf_buf, cum)
                new_params = jax.tree.map(
                    lambda p, b: p - cfg.lr * b, global_params, gmf_buf)
            else:
                new_params = jax.tree.map(jnp.subtract, global_params, cum)
            return new_params, gmf_buf

        if mesh is None:
            @jax.jit
            def step(global_params, cohort_data, rng, gmf_buf):
                return _nova_core(global_params, cohort_data, rng, gmf_buf,
                                  psum_axis=None)
        else:
            from jax.sharding import PartitionSpec as P
            from fedml_tpu.parallel.cohort import make_sharded_stateful_round
            step = make_sharded_stateful_round(
                _nova_core, mesh,
                in_specs=(P(), P("clients"), P(), P()),
                out_specs=(P(), P()))

        self._nova_step = step
        self.cohort_step = self._stateful_step

        if mesh is None:
            # HBM-resident fast path: same _nova_core, cohort gathered by
            # ids inside the jit (the make_device_round pattern) — FedNova
            # joins FedAvg/FedProx/FedOpt on the zero-host-traffic round
            from fedml_tpu.parallel.cohort import gather_live_cohort

            @jax.jit
            def device_step(params, stacked, ids, live, rng, gmf_buf):
                cohort = gather_live_cohort(stacked, ids, live)
                return _nova_core(params, cohort, rng, gmf_buf,
                                  psum_axis=None)

            def _device_wrapper(params, stacked, ids, live, rng):
                if self._gmf_buf is None:
                    self._gmf_buf = jax.tree.map(jnp.zeros_like, params)
                params, self._gmf_buf = device_step(
                    params, stacked, ids, live, rng, self._gmf_buf)
                return params, {}

            self._device_round_override = _device_wrapper

    def _stateful_step(self, params, cohort, rng):
        if self._gmf_buf is None:
            self._gmf_buf = jax.tree.map(jnp.zeros_like, params)
        params, self._gmf_buf = self._nova_step(params, cohort, rng,
                                                self._gmf_buf)
        return params, {}

    # server momentum buffer rides the round checkpoint (bit-identical
    # resume contract, utils/checkpoint.py)
    def _extra_state(self):
        return {"gmf_buf": self._gmf_buf}

    def _extra_state_template(self, params):
        return {"gmf_buf": jax.tree.map(jnp.zeros_like, params)}

    def _load_extra_state(self, extra) -> None:
        self._gmf_buf = extra["gmf_buf"]
