"""What a configuration's ``task`` states, read plainly.

``reference/fedavg.py`` follows a federated round whatever the model is
trained to do; what the model is trained to do stands in the
configuration's file under ``task``, every key of it, with no default:

  loss        ``module:function`` of the loss of one batch,
              ``f(logits, targets, row_mask, **loss_args) -> (sum, weight)``:
              the summed loss of the batch's real entries and the number
              of entries it is a mean over.  A training step descends
              ``sum / max(weight, 1)``; the round-0 evaluation is the sums
              of all its batches over their weights, so the two cannot
              drift apart.
  loss_args   keyword arguments of the loss (may be left out: a loss that
              takes none)
  clip_norm   the global norm a step's gradient is clipped to, or null
  eval_rows   rows a batch of the round-0 evaluation holds
  eval_by     ``population``: every client's rows one after another, cut
              into batches of ``eval_rows``; ``client``: each client's rows
              cut on their own, the last batch of each filled with zero
              rows (for a model whose answer for a row depends on the
              rows beside it, such as experts of limited capacity: the
              batches have to be the program's)

The two losses the program has stand below, written from their definitions
(FedML ``my_model_trainer_classification.py`` and ``my_model_trainer_nwp.py``:
torch's ``CrossEntropyLoss`` over rows, and over positions with the pad id
ignored).  Nothing of ``fedml_tpu`` is imported.  A later configuration
whose task is neither brings its loss in a file of its own and names it.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
from typing import Callable, Optional

import jax
import jax.numpy as jnp

EVAL_BY = ("population", "client")


def _nll(logits, targets):
    """-log softmax(logits)[target], over the last axis."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def row_classification(logits, targets, row_mask):
    """One label a row: ``logits`` [B, C], ``targets`` [B]."""
    return jnp.sum(_nll(logits, targets) * row_mask), jnp.sum(row_mask)


def next_token(logits, targets, row_mask, pad_id: int):
    """One label a position: ``logits`` [B, T, V], ``targets`` [B, T].
    Positions whose target is ``pad_id`` and every position of a masked
    row are left out; the mean is over what is left."""
    weight = (targets != pad_id).astype(jnp.float32) * row_mask[:, None]
    return jnp.sum(_nll(logits, targets) * weight), jnp.sum(weight)


@dataclasses.dataclass(frozen=True)
class Task:
    loss: Callable          # (logits, targets, row_mask) -> (sum, weight)
    clip_norm: Optional[float]
    eval_rows: int
    eval_by: str
    pad_id: Optional[int] = None   # a target the loss leaves out, if any


def from_config(config: dict) -> Task:
    """The configuration's task, or an error that names the missing or
    unknown key."""
    name = config.get("name", "?")
    if "task" not in config:
        raise KeyError(f"configuration {name!r} states no 'task' (see "
                       f"benchmark/reference/tasks.py); there is no default")
    t = config["task"]
    for key in ("loss", "clip_norm", "eval_rows", "eval_by"):
        if key not in t:
            raise KeyError(f"configuration {name!r}: 'task' lacks "
                           f"{key!r}; there is no default")
    mod, _, fn = str(t["loss"]).partition(":")
    try:
        loss = getattr(importlib.import_module(mod), fn)
    except (ImportError, AttributeError, ValueError) as e:
        raise ValueError(f"configuration {name!r}: no implementation of "
                         f"'task.loss' = {t['loss']!r} ({e})") from e
    if t["eval_by"] not in EVAL_BY:
        raise ValueError(f"configuration {name!r}: 'task.eval_by' = "
                         f"{t['eval_by']!r}; have {EVAL_BY}")
    clip = t["clip_norm"]
    loss_args = t.get("loss_args", {})
    return Task(loss=functools.partial(loss, **loss_args),
                clip_norm=None if clip is None else float(clip),
                eval_rows=int(t["eval_rows"]), eval_by=t["eval_by"],
                pad_id=loss_args.get("pad_id"))
