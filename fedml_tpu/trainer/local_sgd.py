"""Local training as one compiled `lax.scan` — the client-side hot loop.

Reference equivalent: the per-client epochs x batches Python loop of
``MyModelTrainer.train`` (fedml_api/distributed/fedavg/MyModelTrainer.py:19-49).
Here the whole local run is a single scan over ``epochs * steps`` so XLA
fuses optimizer updates into the backward pass and the function is
`vmap`-able over a stacked client axis (the cohort engine's trick).

Parity details preserved:
* a *fresh* optimizer per local-training call (the reference constructs the
  optimizer inside ``train`` each round, so Adam moments never persist
  across rounds);
* optional global-norm grad clipping at 1.0 (classification trainer,
  my_model_trainer_classification.py:44);
* batch-mean loss over valid (non-padded) samples only.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from fedml_tpu.obs import telemetry
from fedml_tpu.trainer.workload import Workload

Pytree = Any


def instrument_train_fn(train_fn, epochs: int = 1, registry=None):
    """Wrap a (typically jit'd) ``train(params, data, rng)`` callable with
    trainer telemetry:

    * ``fedml_trainer_compile_seconds`` — the FIRST call's wall time (jit
      trace + XLA compile + run; the "why is round 0 slow" histogram);
    * ``fedml_trainer_train_seconds`` — every later call's wall time
      (blocked until ready, so async dispatch doesn't hide the work);
    * ``fedml_trainer_examples_total`` — valid (mask=1) examples consumed,
      so examples/sec falls out of the snapshot as
      ``examples_total / train_seconds_sum``.  Pass the trainer's
      ``epochs``: the scan revisits every batch each epoch, so one call
      consumes ``epochs * mask.sum()`` examples.

    The wrapper forwards the underlying jit's ``_cache_size`` probe, so
    the flight recorder's `RecompileSentry` (obs/perf.py) can register
    the instrumented function directly and catch a retracing trainer.
    Under ``--device_obs`` the device observatory's wrapper
    (`obs.device.DeviceRecorder.instrument`, applied via
    ``PerfRecorder.instrument_jit``) composes INSIDE this one — it sees
    raw calls for compile/FLOPs accounting while this wrapper keeps the
    blocked-wall-time trainer histograms; both forward the probe.

    With telemetry disabled this returns ``train_fn`` unchanged — zero
    wrapper, zero cost."""
    reg = registry if registry is not None else telemetry.get_registry()
    if not reg.enabled:
        return train_fn
    import threading

    h_compile = reg.histogram("fedml_trainer_compile_seconds")
    h_train = reg.histogram("fedml_trainer_train_seconds")
    c_examples = reg.counter("fedml_trainer_examples_total")
    # claimed under a lock: concurrent silo threads (the chaos CLI's
    # threaded drive) may both make their first call during the one jit
    # compile — exactly one sample may land in the compile histogram
    state = {"first": True}
    state_lock = threading.Lock()
    epochs = max(int(epochs), 1)

    def instrumented(params, data, rng):
        t0 = time.perf_counter()
        out = train_fn(params, data, rng)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        with state_lock:
            first, state["first"] = state["first"], False
        (h_compile if first else h_train).observe(dt)
        mask = data.get("mask") if isinstance(data, dict) else None
        if mask is not None:
            import numpy as np
            c_examples.inc(epochs * float(np.asarray(mask).sum()))
        return out

    cache_size = getattr(train_fn, "_cache_size", None)
    if cache_size is not None:
        instrumented._cache_size = cache_size
    return instrumented


def make_local_trainer(workload: Workload,
                       optimizer: optax.GradientTransformation,
                       epochs: int, prox_mu: float = 0.0,
                       grad_reduce=None, scan_unroll: int = 1):
    """Returns ``train(params, data, rng) -> (new_params, metrics)``.

    ``data`` leaves are [S, B, ...] (S batches of size B) with ``mask``
    [S, B]; the scan runs epochs*S steps, revisiting the same batches each
    epoch in order (the reference's DataLoader order is fixed per round).

    ``prox_mu`` adds the FedProx proximal gradient mu*(w - w_global) each
    step (w_global = the params this call started from).  NOTE the reference's
    *distributed fedprox* omits this term entirely (SURVEY.md §2.2 caveat —
    its trainer is vanilla SGD); we implement the actual algorithm (Li et al.
    2020), matching the mu usage in the reference's FedNova optimizer
    (fednova.py:133-136).

    ``grad_reduce(grads) -> grads`` runs right after the backward pass,
    before prox/clip/optimizer.  Sequence-parallel training uses it to
    `psum` the per-shard partial gradients over the ``sequence`` mesh axis
    (each shard's backward only sees its own logits' contribution to the
    psum'd loss; parallel/sequence.py).

    A step whose batch holds no row (``mask`` sums to 0: a short client
    padded to the population's step count, every step of a padded cohort
    slot) leaves params, state and optimizer state as they came; only the
    key chain advances, so a later step draws the key it would draw
    anyway.  The step is a `lax.cond` on that sum, and what it costs
    follows from how the trainer is run:

    * in sequence (one client's jit, the cohort engine's `lax.scan` over a
      conv model's clients) the predicate is a scalar and XLA emits a
      conditional: the empty step's forward, backward and update do not
      run;
    * under `jax.vmap` the predicate is batched and JAX lowers the `cond`
      to a select over both branches: every client computes every step
      and the empty ones are thrown away, as one client alone cannot
      leave a batched step;
    * given ``grad_reduce`` the step is computed unconditionally and then
      frozen by a select: a collective must be entered by every shard.

    ``train_loss_per_step`` reads 0 for an empty step in the first two
    (the loss function is not asked), and what ``loss_fn`` gives a fully
    masked batch in the third: the masked mean's 0 plus any term beside
    it (a mixture's balance term).  The wave engine drops the metric.

    A workload whose loss counts things (``workload.counter_shapes``, name
    -> shape; the loss's aux carries them under ``"counters"``) has them
    summed over the steps that ran into ``metrics["counters"]``.

    ``scan_unroll`` is forwarded to the step `lax.scan` — the default 1
    keeps the compiled program small; the full trip count lets XLA cost
    analysis (which counts a scan body once) see every step."""
    clip = (optax.clip_by_global_norm(workload.grad_clip_norm)
            if workload.grad_clip_norm is not None else None)
    stateful = workload.stateful
    counter_shapes = dict(workload.counter_shapes or {})

    # Gradients are taken over the TRAINED collection only.  For stateful
    # workloads the non-trained collections (BatchNorm running stats) ride
    # the scan carry beside the optimizer state — never differentiated,
    # never seen by the optimizer — and the updated stats come back through
    # the loss aux ("state", workload.py).
    if stateful:
        def _loss(trained, state, batch, rng):
            return workload.loss_fn({"params": trained, **state}, batch, rng,
                                    True)
    else:
        def _loss(trained, state, batch, rng):
            return workload.loss_fn(trained, batch, rng, True)
    grad_fn = jax.value_and_grad(_loss, has_aux=True)

    def train(params: Pytree, data: Dict[str, jax.Array], rng: jax.Array
              ) -> Tuple[Pytree, Dict[str, jax.Array]]:
        if stateful:
            trained = params["params"]
            state = {k: v for k, v in params.items() if k != "params"}
        else:
            trained, state = params, {}
        init_trained = trained
        opt_state = optimizer.init(trained)
        clip_state = clip.init(trained) if clip is not None else None
        num_steps = jax.tree.leaves(data)[0].shape[0]

        def step(carry, step_idx):
            trained, state, opt_state, rng = carry
            # what advances on every step stays outside the branch: the
            # key chain (a later step draws the key it would draw with no
            # empty step before it) and the batch's slice
            rng, dropout_rng = jax.random.split(rng)
            batch = jax.tree.map(lambda x: x[step_idx % num_steps], data)
            rows = jnp.sum(batch["mask"])
            got_data = rows > 0

            def do_step(trained, state, opt_state):
                (loss, aux), grads = grad_fn(trained, state, batch,
                                             dropout_rng)
                if grad_reduce is not None:
                    grads = grad_reduce(grads)
                if prox_mu:
                    grads = jax.tree.map(
                        lambda g, p, p0: g + prox_mu * (p - p0),
                        grads, trained, init_trained)
                if clip is not None:
                    grads, _ = clip.update(grads, clip_state)
                updates, new_opt_state = optimizer.update(grads, opt_state,
                                                          trained)
                new_trained = optax.apply_updates(trained, updates)
                new_state = aux["state"] if stateful else state
                counted = {k: aux["counters"][k].astype(jnp.float32)
                           for k in counter_shapes}
                return new_trained, new_state, new_opt_state, (loss, counted)

            if grad_reduce is not None:
                # a collective must be entered by every shard, whatever its
                # own batch holds: compute, then freeze (grads are 0 on a
                # fully padded batch for SGD, but Adam's eps would still
                # drift the params)
                *new, loss = do_step(trained, state, opt_state)
                trained, state, opt_state = jax.tree.map(
                    lambda n, o: jnp.where(got_data, n, o),
                    tuple(new), (trained, state, opt_state))
                return (trained, state, opt_state, rng), loss

            def keep_carry(trained, state, opt_state):
                # the zero is made from the mask's sum, not from a literal:
                # under shard_map both branches must vary over the same axes
                return trained, state, opt_state, (rows * 0, {
                    k: jnp.zeros(shape, jnp.float32) + rows * 0
                    for k, shape in counter_shapes.items()})

            trained, state, opt_state, loss = jax.lax.cond(
                got_data, do_step, keep_carry, trained, state, opt_state)
            return (trained, state, opt_state, rng), loss

        total_steps = epochs * num_steps
        (trained, state, _, _), (losses, counted) = jax.lax.scan(
            step, (trained, state, opt_state, rng), jnp.arange(total_steps),
            unroll=scan_unroll)
        out = {"params": trained, **state} if stateful else trained
        metrics = {"train_loss_per_step": losses}
        if counter_shapes:
            metrics["counters"] = jax.tree.map(lambda c: jnp.sum(c, axis=0),
                                               counted)
        return out, metrics

    return train


def make_evaluator(workload: Workload):
    """Returns ``evaluate(params, data) -> summed metrics`` over [S, B, ...]
    batch stacks.  Mirrors ``MyModelTrainer.test`` (MyModelTrainer.py:51-90)
    but runs as one scan; metrics are sums so they aggregate exactly across
    clients/devices with a plain psum."""

    def evaluate(params: Pytree, data: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        def step(carry, batch):
            m = workload.metric_fn(params, batch)
            return jax.tree.map(jnp.add, carry, m), None

        first = jax.tree.map(lambda x: x[0], data)
        init = jax.tree.map(jnp.zeros_like, workload.metric_fn(params, first))
        out, _ = jax.lax.scan(step, init, data)
        return out

    return evaluate
