"""Plain FedAvg (McMahan et al. 2017), the yardstick `correct` is read from.

One client after another, one mini-batch after another, in float32 with
its products at the precision the configuration states (``precision``; a
cell's is ``default``, which on the TPU is one bfloat16 pass with float32
sums: a reference at ``highest`` lies 0.4 % from every float32 run there,
further than the bfloat16 control does, and so cannot tell them apart): no
vmap over clients, no scan, no padding to the longest client, no waves, no
streaming fold.  It imports
nothing of ``fedml_tpu`` and takes nothing the program made; what it shares
with the program is JAX's and flax's public random-number derivation, which
is what lets it start from the same weights and drop the same units:

  key            = jax.random.key(seed); key, init_key = split(key)
  each round:      key, round_key = split(key)
  cohort:          all clients when the cohort is the population, else
                   numpy RandomState(round).choice(range(N), m, False)
                   (FedML's published sampler)
  client in slot j: ck = fold_in(round_key, j);
  each step:       ck, dropout_key = split(ck)

A client's local run (FedML ``MyModelTrainer``): E=1 pass over its rows in
order in batches of B (the last one short), plain SGD on the batch's mean
loss.  The round's global is the mean of the clients' results weighted by
their row counts.  What the loss of a batch is, what norm its gradient is
clipped to and how the round-0 evaluation is cut into batches is the
configuration's to state (``task``, read by ``reference/tasks.py``): this
file holds none of it.  A model that sows terms into flax's ``losses``
collection while training (an expert layer's balance term, already weighted)
has them added to the batch's loss; the evaluation leaves them out.  What
the file does not follow it refuses before any round (``FOLLOWS``).

``fault`` plants one of the faults the benchmark's comparison has to catch,
so that their readings can be taken from the reference put in the
program's place.

The comparison (``compare``) walks the leaves of the kept globals of both
sides together and holds one leaf's float64 differences at a time, so the
host memory it adds is a leaf's, not a tree's.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

FAULTS = ("half_batch", "half_tokens", "state_unchanged")
# the program's options this file follows, by the CLI's own key: any other
# value is another algorithm, and following this one instead would compare
# the program with something it was not asked to do
FOLLOWS = {"client_optimizer": "sgd", "server_opt": "plain", "epochs": 1}


def refuse_unfollowed(cli: dict) -> None:
    """An error that names the key, where the cell's CLI arguments leave
    out or state otherwise what ``FOLLOWS`` holds."""
    for key, only in FOLLOWS.items():
        if key not in cli:
            raise KeyError(f"the cell's cli states no {key!r}; the "
                           f"reference follows {key}={only!r} only")
        if type(only)(cli[key]) != only:
            raise ValueError(f"the reference follows {key}={only!r} only, "
                             f"the cell states {key}={cli[key]!r} (see "
                             f"PERF.md)")


def sample_cohort(round_idx: int, population: int, cohort: int) -> np.ndarray:
    if population == cohort:
        return np.arange(population, dtype=np.int64)
    rng = np.random.RandomState(round_idx)
    return rng.choice(range(population), min(cohort, population),
                      replace=False)


def _pad(x: np.ndarray, rows: int) -> np.ndarray:
    if len(x) == rows:
        return x
    out = np.zeros((rows,) + x.shape[1:], x.dtype)
    out[:len(x)] = x
    return out


def make_steps(model, lr: float, task, dtype=None):
    """(train_step, eval_batch, accumulate) for ``model`` under ``task``
    (a ``tasks.Task``).  ``dtype`` computes the model in a lower
    precision: weights and real-valued inputs are cast to it, the loss and
    the update stay float32."""
    clip = task.clip_norm

    def apply(params, x, train, rng):
        """(logits, what the model sowed into ``losses``)."""
        if dtype is not None:
            params = jax.tree.map(lambda p: p.astype(dtype), params)
            if jnp.issubdtype(x.dtype, jnp.floating):
                x = x.astype(dtype)
        kw = {"rngs": {"dropout": rng}} if train else {}
        logits, sown = model.apply({"params": params}, x, train=train,
                                   mutable=["losses"], **kw)
        return (logits.astype(jnp.float32),
                sum(jax.tree.leaves(sown.get("losses", {})), 0.0))

    def loss_fn(params, x, y, mask, rng):
        logits, sown = apply(params, x, True, rng)
        total, weight = task.loss(logits, y, mask)
        return total / jnp.maximum(weight, 1.0) + sown

    @jax.jit
    def train_step(params, x, y, mask, key):
        key, dropout_key = jax.random.split(key)
        grads = jax.grad(loss_fn)(params, x, y, mask, dropout_key)
        scale = 1.0
        if clip is not None:
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                                for g in jax.tree.leaves(grads)))
            scale = jnp.where(norm < clip, 1.0, clip / norm)
        params = jax.tree.map(lambda p, g: p - lr * scale * g, params, grads)
        return params, key

    @jax.jit
    def eval_batch(params, x, y, mask):
        return task.loss(apply(params, x, False, None)[0], y, mask)

    @jax.jit
    def accumulate(acc, params, weight):
        return jax.tree.map(lambda a, q: a + weight * q, acc, params)

    return train_step, eval_batch, accumulate


def eval_batches(clients, rows: int, by: str):
    """The round-0 evaluation's batches (x, y, mask), each of ``rows``
    rows: the population's rows one after another, or each client's cut
    on their own; a short last batch is filled with zero rows."""
    if by == "population":
        pools = [(np.concatenate([c[0] for c in clients]),
                  np.concatenate([c[1] for c in clients]))]
    else:
        pools = [c for c in clients if len(c[1])]
    for xs, ys in pools:
        for lo in range(0, len(ys), rows):
            yb = ys[lo:lo + rows]
            yield (_pad(xs[lo:lo + rows], rows), _pad(yb, rows),
                   _pad(np.ones(len(yb), np.float32), rows))


def run(model, clients: Sequence[Tuple[np.ndarray, np.ndarray]], *,
        task, seed: int, rounds: int, cohort: int, batch_size: int,
        lr: float, dtype=None, fault: Optional[str] = None,
        precision: str = "highest",
        log: Callable[[str], None] = lambda s: None) -> dict:
    """Follow ``rounds`` rounds from the seed under ``task`` (a
    ``tasks.Task``).  Returns ``states``, the globals the comparison reads
    as host trees by round index: {0: g0, 1: g1, rounds: the last}, and
    ``loss_r0``, the task's mean loss of g1 over every client's training
    rows."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")
    if fault == "half_tokens" and task.pad_id is None:
        raise ValueError("half_tokens needs a task whose loss leaves out a "
                         "pad id")
    B = batch_size
    with jax.default_matmul_precision(precision):
        train_step, eval_batch, accumulate = make_steps(model, lr, task,
                                                        dtype)
        key = jax.random.key(seed)
        key, init_key = jax.random.split(key)
        x0 = jnp.asarray(_pad(clients[0][0][:B], B))
        params = model.init(init_key, x0)["params"]
        states: Dict[int, object] = {0: jax.tree.map(np.asarray, params)}
        loss_r0 = None
        ones = np.ones(B, np.float32)
        for r in range(rounds):
            key, round_key = jax.random.split(key)
            ids = sample_cohort(r, len(clients), cohort)
            acc = jax.tree.map(jnp.zeros_like, params)
            total = 0.0
            for slot, cid in enumerate(ids):
                x, y = clients[int(cid)]
                n = len(y)
                if n == 0:
                    continue
                ck = jax.random.fold_in(round_key, slot)
                p = params
                for lo in range(0, n, B):
                    xb, yb = x[lo:lo + B], y[lo:lo + B]
                    m = ones if len(yb) == B else _pad(
                        np.ones(len(yb), np.float32), B)
                    if fault == "half_batch":
                        # half of each batch left out, the mean taken
                        # over the rest
                        m = m * (np.arange(B) % 2 == 0)
                    if fault == "half_tokens":
                        # every other target made the pad, which the loss
                        # leaves out: half of a batch of one row
                        yb = yb.copy()
                        yb[..., 1::2] = task.pad_id
                    p, ck = train_step(p, _pad(xb, B), _pad(yb, B), m, ck)
                acc = accumulate(acc, p, float(n))
                total += float(n)
            new = jax.tree.map(lambda a: a / total, acc)
            if fault != "state_unchanged":
                params = new
            if r in (0, rounds - 1):
                states[r + 1] = jax.tree.map(np.asarray, params)
            log(f"reference round {r}: {len(ids)} clients, {int(total)} rows")
            if r == 0:
                parts = [eval_batch(params, *b) for b in eval_batches(
                    clients, task.eval_rows, task.eval_by)]
                loss_r0 = (sum(float(s) for s, _ in parts)
                           / sum(float(w) for _, w in parts))
    return {"states": states, "loss_r0": loss_r0}


# ---------------------------------------------------------------------------
# the numbers compared

def _change_norms(now, then):
    """(now - then) in float64 and its norm: the float32 leaves are
    converted as the subtraction reads them, so no float64 copy of either
    is made."""
    change = np.subtract(now, then, dtype=np.float64)
    return change, np.linalg.norm(change.ravel())


def leaf_norms(prog: Dict[int, object], ref: Dict[int, object],
               rounds: Sequence[int]) -> Dict[str, Dict[int, np.ndarray]]:
    """For each round j of ``rounds``, three vectors of leaf count, in the
    flattened-tree order: the norm of the reference's change of a leaf
    since g0 (``ref``), of the program's (``prog``), and of the difference
    of the two changes (``diff``).  One leaf is held in float64 at a
    time: a leaf's two changes."""
    out = {kind: {j: [] for j in rounds} for kind in ("ref", "prog", "diff")}
    leaves = {(side, j): jax.tree.leaves(states[j])
              for side, states in (("prog", prog), ("ref", ref))
              for j in {0, *rounds}}
    for i in range(len(leaves["ref", 0])):
        for j in rounds:
            c_ref, n_ref = _change_norms(leaves["ref", j][i],
                                         leaves["ref", 0][i])
            c_prog, n_prog = _change_norms(leaves["prog", j][i],
                                           leaves["prog", 0][i])
            np.subtract(c_prog, c_ref, out=c_prog)
            out["ref"][j].append(n_ref)
            out["prog"][j].append(n_prog)
            out["diff"][j].append(np.linalg.norm(c_prog.ravel()))
            del c_ref, c_prog     # before the next two are made
    return {kind: {j: np.asarray(v) for j, v in by.items()}
            for kind, by in out.items()}


def _worst_leaf_gap(prog, ref, keep=None) -> float:
    """Worst leaf's gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    gap = np.abs(prog - ref) / np.maximum(ref, np.median(ref))
    return float((gap if keep is None else gap[keep]).max())


def compare(prog_states: Dict[int, object], prog_loss_r0: float,
            ref: dict) -> dict:
    """The numbers `correct` is decided on, program against reference.

    ``prog_states``: the program's globals by round index, {0: g0, 1: g1,
    K: gK} (K >= 1), as host trees with the reference's layout; the
    reference's ``states`` are kept alike, for the same rounds.  Returns
    name -> reading."""
    rs = ref["states"]
    if set(prog_states) != set(rs) or not {0, 1} <= set(rs):
        raise ValueError(f"the program kept the globals of rounds "
                         f"{sorted(prog_states)}, the reference of "
                         f"{sorted(rs)}; both need 0, 1 and the same last")
    if jax.tree.structure(prog_states[0]) != jax.tree.structure(rs[0]):
        raise ValueError(
            "the program's parameter tree is not laid out as the "
            "reference's: "
            f"{jax.tree.structure(prog_states[0])} vs "
            f"{jax.tree.structure(rs[0])}")
    k = max(rs)
    rounds = sorted({1, k})
    norms = leaf_norms(prog_states, rs, rounds)
    out = {"loss_r0": abs(prog_loss_r0 - ref["loss_r0"])
           / abs(ref["loss_r0"])}
    g_ref = norms["ref"][1]
    out["grad1_worst_leaf"] = _worst_leaf_gap(norms["prog"][1], g_ref)
    # leaves whose first pseudo-gradient is nought to rounding in the
    # reference are left out of the change (contract, step 4)
    keep = g_ref >= 1e-3 * np.median(g_ref)
    out[f"change{k}_worst_leaf"] = _worst_leaf_gap(
        norms["prog"][k], norms["ref"][k], keep)
    # not a norm gap but the norm of the difference of the two changes: it
    # also sees a change of the right size in the wrong direction (a
    # clipped gradient keeps its norm whatever the batch holds).  Over the
    # whole tree (``_diff``), and the median leaf's, each leaf against its
    # reference norm or the median leaf's (``_median_leaf``): a few small
    # leaves whose gradient is a sum that all but cancels (GroupNorm's 64
    # scales) carry the whole tree's on some seeds, the median leaf is
    # steady from seed to seed (PERF.md section 6)
    for j in rounds:
        diff, n_ref = norms["diff"][j], norms["ref"][j]
        out[f"change{j}_diff"] = float(
            np.sqrt(np.sum(diff ** 2)) / np.sqrt(np.sum(n_ref ** 2)))
        out[f"change{j}_median_leaf"] = float(np.median(
            (diff / np.maximum(n_ref, np.median(n_ref)))[keep]))
    return out


def verdict(numbers: dict, limits: dict) -> Tuple[bool, List[dict]]:
    """Each number compared, beside its limit; correct iff all hold."""
    rows = []
    ok = True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and np.isfinite(value) and value <= limit
        ok = ok and good
        rows.append({"name": name, "value": value, "limit": limit,
                     "ok": bool(good)})
    return ok, rows
