"""Client workload contract — the TPU-native ``ModelTrainer``.

The reference seam is the framework-neutral ``ModelTrainer`` ABC
(``fedml_core/trainer/model_trainer.py:4-37``: get/set params, train, test).
Here the seam is *functional*: a `Workload` bundles pure functions
(init / loss / metrics) over a flax model, so trainers can `jax.grad`,
`vmap` (stacked clients), and `shard_map` (mesh-sharded cohorts) it.

The three concrete workloads mirror the reference's three trainer flavors
(fedml_api/standalone/fedavg/my_model_trainer_{classification,nwp,
tag_prediction}.py):

* `ClassificationWorkload` — softmax CE, top-1 accuracy, grad-clip 1.0
  (my_model_trainer_classification.py:44).
* `NWPWorkload` — per-position softmax CE over sequence logits, ignoring
  padding-id targets (next-word/char prediction).
* `TagPredictionWorkload` — multi-label: BCE-with-logits, exact-match +
  precision/recall (my_model_trainer_tag_prediction.py; eval thresholds at
  0.5 like MyModelTrainer.test, MyModelTrainer.py:76-82).

Batches are dicts ``{"x": [B, ...], "y": [B, ...], "mask": [B]}``; the mask
makes padded cohort batches exact — a padded row contributes nothing to loss,
gradient, or metrics, so sample-weighted FedAvg stays bit-honest.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import optax

Pytree = Any
Batch = Dict[str, jax.Array]


def make_client_optimizer(name: str, lr: float, wd: float = 0.0) -> optax.GradientTransformation:
    """Client optimizer parity (my_model_trainer_classification.py:27-31):
    "sgd" -> plain SGD(lr); anything else -> Adam(lr, weight_decay=wd,
    amsgrad=True).  Torch couples wd into the gradient before the moment
    updates, so add_decayed_weights precedes the amsgrad transform."""
    if name == "sgd":
        return optax.sgd(lr)
    return optax.chain(
        optax.add_decayed_weights(wd),
        optax.scale_by_amsgrad(),
        optax.scale(-lr),
    )


@dataclasses.dataclass(frozen=True)
class Workload:
    """Pure-function training contract.

    loss_fn(params, batch, rng, train) -> (scalar loss, aux dict).  For
    stateful models (BatchNorm running stats) aux carries ``"state"``: the
    updated non-trained collections, which the local trainer splices back
    into params after the optimizer step (local_sgd.py).  FedAvg then
    averages running stats along with weights — exactly what the reference's
    state_dict averaging does (FedAVGAggregator.py:72-80 iterates ALL
    state_dict keys, stats included).

    metric_fn(params, batch) -> dict of *summable* metrics
    (must include "correct", "loss_sum", "total").
    """
    model: Any  # flax linen module
    loss_fn: Callable[[Pytree, Batch, jax.Array, bool], tuple]
    metric_fn: Callable[[Pytree, Batch], Dict[str, jax.Array]]
    grad_clip_norm: Optional[float] = None
    stateful: bool = False  # params = full variables dict incl. batch_stats
    # name -> shape of what the loss counts beside its value: float32
    # arrays under ``aux["counters"]``, summed over a local run's steps by
    # the local trainer (an expert layer's token counts)
    counter_shapes: Optional[Dict[str, tuple]] = None

    def init(self, rng: jax.Array, sample_batch: Batch) -> Pytree:
        init = self.model.init
        if getattr(self.model, "arch", None) is not None:
            # a published-size decoder's first forward pass, op by op,
            # is hundreds of programs (4 min on the chip, PR 37): one
            init = jax.jit(init)
        variables = init(rng, sample_batch["x"])
        if self.stateful:
            return dict(variables)
        return variables["params"]

    def apply(self, params: Pytree, x: jax.Array, train: bool = False,
              rng: Optional[jax.Array] = None) -> jax.Array:
        kwargs = {}
        if rng is not None:
            kwargs["rngs"] = {"dropout": rng}
        variables = params if self.stateful else {"params": params}
        if self.stateful and train:
            out, _ = self.model.apply(variables, x, train=True,
                                      mutable=["batch_stats"], **kwargs)
            return out
        return self.model.apply(variables, x, train=train, **kwargs)


def _masked_mean(values: jax.Array, mask: jax.Array) -> jax.Array:
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.sum(values * mask) / denom


def cast_floats(tree: Pytree, dtype) -> Pytree:
    """Cast floating leaves to ``dtype`` (ints/keys untouched)."""
    return jax.tree.map(
        lambda v: v.astype(dtype)
        if jnp.issubdtype(jnp.asarray(v).dtype, jnp.floating) else v, tree)


def ClassificationWorkload(model, num_classes: int,
                           grad_clip_norm: Optional[float] = 1.0,
                           stateful: bool = False,
                           compute_dtype=None) -> Workload:
    """Softmax cross-entropy on logits, batch-mean over valid rows (the
    torch ``nn.CrossEntropyLoss()`` default reduction).  ``stateful=True``
    for BatchNorm models: params is the full variables dict and updated
    running stats ride the loss aux (see Workload docstring).

    ``compute_dtype=jnp.bfloat16`` enables mixed precision the TPU way
    (SURVEY.md "MXU" guidance): master params, gradients, and the optimizer
    stay f32; the forward/backward model compute — conv/matmul inputs AND
    weights — is cast to bf16, halving HBM traffic and doubling MXU rate.
    The CE loss is always computed in f32 (softmax is range-sensitive)."""

    def loss_fn(params, batch, rng, train):
        kwargs = {"rngs": {"dropout": rng}} if rng is not None else {}
        x = batch["x"]
        if compute_dtype is not None:
            if stateful:
                # keep BatchNorm running stats f32: their momentum update
                # adds increments far below bf16's 8-bit mantissa
                params = {k: (v if k == "batch_stats"
                              else cast_floats(v, compute_dtype))
                          for k, v in params.items()}
            else:
                params = cast_floats(params, compute_dtype)
            if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
                x = x.astype(compute_dtype)
        if stateful:
            logits, new_state = model.apply(
                params, x, train=train,
                mutable=["batch_stats"], **kwargs)
        else:
            logits = model.apply({"params": params}, x,
                                 train=train, **kwargs)
        logits = logits.astype(jnp.float32)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, batch["y"])
        loss = _masked_mean(ce, batch["mask"])
        aux = {"loss": loss}
        if stateful:
            new_state = dict(new_state)
            if compute_dtype is not None:
                # running stats rejoin the f32 master tree
                new_state = cast_floats(new_state, jnp.float32)
            aux["state"] = new_state
        return loss, aux

    def metric_fn(params, batch):
        variables = params if stateful else {"params": params}
        logits = model.apply(variables, batch["x"], train=False)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, batch["y"])
        pred = jnp.argmax(logits, axis=-1)
        mask = batch["mask"]
        out = {
            "correct": jnp.sum((pred == batch["y"]) * mask),
            "loss_sum": jnp.sum(ce * mask),
            "total": jnp.sum(mask),
        }
        if num_classes > 5:
            # top-5 parity with the reference's accTop5 curves
            # (pretrained/*/train_metrics)
            top5 = jax.lax.top_k(logits, 5)[1]
            in5 = jnp.any(top5 == batch["y"][..., None], axis=-1)
            out["correct_top5"] = jnp.sum(in5 * mask)
        return out

    return Workload(model=model, loss_fn=loss_fn, metric_fn=metric_fn,
                    grad_clip_norm=grad_clip_norm, stateful=stateful)


def make_nwp_loss_metrics(forward, pad_id: int = 0):
    """THE single home of the NWP loss/metric semantics: per-position CE
    averaged over non-pad positions of valid rows, plus summable
    correct/loss_sum/total metrics (my_model_trainer_nwp.py semantics,
    where torch CE with [B, V, T] logits means per-position CE).

    ``forward(params, x, rng, train) -> (logits [B, T, V], extra_loss)``
    abstracts the model application — NWPWorkload's flax apply (with
    dtype casting and the MoE balance-loss capture riding ``extra_loss``)
    and the pipeline workload's GPipe forward (parallel/pipeline.py) both
    build on this, so the masking/metric math cannot drift between them.
    """

    def _position_mask(batch):
        tok_valid = (batch["y"] != pad_id).astype(jnp.float32)
        return tok_valid * batch["mask"][:, None]

    def loss_fn(params, batch, rng, train):
        logits, extra, *counted = forward(params, batch["x"], rng, train)
        logits = logits.astype(jnp.float32)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, batch["y"])
        m = _position_mask(batch)
        loss = jnp.sum(ce * m) / jnp.maximum(jnp.sum(m), 1.0) + extra
        aux = {"loss": loss}
        if counted:
            aux["counters"] = counted[0]
        return loss, aux

    def metric_fn(params, batch):
        logits, *_ = forward(params, batch["x"], None, False)
        logits = logits.astype(jnp.float32)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, batch["y"])
        pred = jnp.argmax(logits, axis=-1)
        m = _position_mask(batch)
        return {
            "correct": jnp.sum((pred == batch["y"]) * m),
            "loss_sum": jnp.sum(ce * m),
            "total": jnp.sum(m),
        }

    return loss_fn, metric_fn


def NWPWorkload(model, pad_id: int = 0,
                grad_clip_norm: Optional[float] = None,
                compute_dtype=None) -> Workload:
    """Next-word/char prediction over [B, T, V] logits
    (make_nwp_loss_metrics has the loss semantics).

    ``compute_dtype=jnp.bfloat16``: casts params for bf16 weight loads and
    f32 master/CE as in ClassificationWorkload — but flax RNN cells promote
    to their own ``dtype``, so the MODEL must also be built with
    ``dtype=bfloat16`` (RNNOriginalFedAvg/RNNStackOverflow take it;
    create_workload wires both) or the recurrent matmuls stay f32."""

    arch = getattr(model, "arch", None)
    # what a --model_config model's layers count a step, by the
    # collection each sows into (the arch says: every attention its core
    # and whether the fused kernels took it, an indexer its pairs, every
    # expert layer its tokens)
    counted = {} if arch is None else arch.counters

    def forward(params, x, rng, train):
        if compute_dtype is not None:
            params = cast_floats(params, compute_dtype)
        if arch is not None and train:
            # a --model_config model sows its loss terms (a multi-token
            # prediction module's) already weighted, and its layers their
            # counts: summed over the layers
            logits, sown = model.apply(
                {"params": params}, x, train=train,
                mutable=["losses"] + [c for c, _ in counted.values()])
            extra = sum(jax.tree.leaves(sown.get("losses", {})), 0.0)
            return logits, extra, {k: sum(jax.tree.leaves(sown[c]))
                                   for k, (c, _) in counted.items()}
        if getattr(model, "moe_experts", 0) and train:
            # capture the Switch load-balance terms sown per MoE layer
            # (models/moe.py); plain applies elsewhere no-op the sow.
            # Switch eq. 4: each layer's aux SUMS into the loss at weight
            # alpha (not a mean — a deeper stack gets more total pressure)
            logits, sown = model.apply({"params": params}, x,
                                       train=train, mutable=["losses"])
            extra = model.moe_aux_weight * sum(
                jax.tree.leaves(sown.get("losses", {})))
            return logits, extra
        return model.apply({"params": params}, x, train=train), 0.0

    loss_fn, metric_fn = make_nwp_loss_metrics(forward, pad_id)
    return Workload(model=model, loss_fn=loss_fn, metric_fn=metric_fn,
                    grad_clip_norm=grad_clip_norm,
                    counter_shapes={k: shape for k, (_, shape)
                                    in counted.items()} or None)


def TagPredictionWorkload(model, grad_clip_norm: Optional[float] = None) -> Workload:
    """Multi-label tag prediction (stackoverflow_lr): BCE-with-logits loss;
    eval thresholds sigmoid>0.5 with exact-match accuracy plus summed
    precision/recall (MyModelTrainer.test, MyModelTrainer.py:76-82)."""

    def loss_fn(params, batch, rng, train):
        logits = model.apply({"params": params}, batch["x"], train=train)
        bce = jnp.mean(optax.sigmoid_binary_cross_entropy(logits, batch["y"]), axis=-1)
        loss = _masked_mean(bce, batch["mask"])
        return loss, {"loss": loss}

    def metric_fn(params, batch):
        logits = model.apply({"params": params}, batch["x"], train=False)
        bce = jnp.mean(optax.sigmoid_binary_cross_entropy(logits, batch["y"]), axis=-1)
        mask = batch["mask"]
        pred = (logits > 0.0).astype(jnp.float32)  # sigmoid(z) > .5 <=> z > 0
        y = batch["y"]
        exact = jnp.all(pred == y, axis=-1).astype(jnp.float32)
        tp = jnp.sum(y * pred, axis=-1)
        precision = tp / (jnp.sum(pred, axis=-1) + 1e-13)
        recall = tp / (jnp.sum(y, axis=-1) + 1e-13)
        return {
            "correct": jnp.sum(exact * mask),
            "loss_sum": jnp.sum(bce * mask),
            "total": jnp.sum(mask),
            "precision_sum": jnp.sum(precision * mask),
            "recall_sum": jnp.sum(recall * mask),
        }

    return Workload(model=model, loss_fn=loss_fn, metric_fn=metric_fn,
                    grad_clip_norm=grad_clip_norm)
