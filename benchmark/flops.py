"""Operations a model requires, from shapes alone.

Forward FLOPs of one sample = 2 x the multiply-accumulates of every
convolution and matrix product in the reference model's forward pass, read
from its jaxpr (shapes only: nothing is compiled or run).  A training step
requires three times that (forward, gradient by the inputs, gradient by the
weights); recomputed and masked-out work does not count.  Element-wise work
(norms, activations, pooling, the loss) is left out, as is usual for a
model-FLOPs utilization, so the figure is a few percent under XLA's own
count of one forward pass (the unit test holds the two together).

The walk counts what the plain reference computes, and that is what the
model requires only while the reference computes nothing it then throws
away.  A plain reference of an expert layer computes every expert for every
token and masks; the model requires the experts a token is sent to.  Such a
configuration's ``<name>.py`` exports its own count,
``forward_macs_per_sample(config, sample_shape)``: the multiply-accumulates
one sample's forward pass requires, kept with the benchmark beside the model
it counts.  Where it is there it is used, and the walk otherwise.
"""

from __future__ import annotations

import numpy as np


def _dot_flops(eqn) -> float:
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs, rhs = (v.aval.shape for v in eqn.invars)
    batch = np.prod([lhs[i] for i in lb]) if lb else 1
    contract = np.prod([lhs[i] for i in lc]) if lc else 1
    lfree = np.prod([d for i, d in enumerate(lhs)
                     if i not in lc and i not in lb])
    rfree = np.prod([d for i, d in enumerate(rhs)
                     if i not in rc and i not in rb])
    return 2.0 * batch * contract * lfree * rfree


def _conv_flops(eqn) -> float:
    dn = eqn.params["dimension_numbers"]
    rhs = eqn.invars[1].aval.shape
    out = eqn.outvars[0].aval.shape
    k_spatial = np.prod([rhs[i] for i in dn.rhs_spec[2:]])
    c_in_per_group = rhs[dn.rhs_spec[1]]
    # every output element is k_spatial * c_in_per_group MACs
    return 2.0 * np.prod(out) * k_spatial * c_in_per_group


def _walk(jaxpr) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            total += _dot_flops(eqn)
        elif eqn.primitive.name == "conv_general_dilated":
            total += _conv_flops(eqn)
        for sub in eqn.params.values():
            inner = getattr(sub, "jaxpr", None)
            if inner is not None:
                total += _walk(inner if hasattr(inner, "eqns")
                               else inner.jaxpr)
    return total


def forward_flops_per_sample(model, sample_shape, dtype="float32",
                             batch: int = 8) -> float:
    """Matmul and convolution FLOPs of ``model``'s forward pass, a sample,
    for inputs of ``dtype`` (token ids are integers)."""
    import jax
    import jax.numpy as jnp
    x = jax.ShapeDtypeStruct((batch,) + tuple(sample_shape), jnp.dtype(dtype))
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros(x.shape, x.dtype))["params"])
    jaxpr = jax.make_jaxpr(
        lambda p, v: model.apply({"params": p}, v, train=False))(params, x)
    return _walk(jaxpr.jaxpr) / batch


def train_flops_per_sample(reference, config: dict, sample_shape,
                           dtype="float32") -> float:
    """FLOPs one sample's training step requires: three forward passes'
    worth.  ``reference`` is the configuration's ``<name>.py``: its own
    count of required forward multiply-accumulates where it exports one,
    the walk over its model's jaxpr otherwise."""
    counted = getattr(reference, "forward_macs_per_sample", None)
    if counted is not None:
        return 3.0 * 2.0 * float(counted(config, tuple(sample_shape)))
    return 3.0 * forward_flops_per_sample(reference.build_model(config),
                                          sample_shape, dtype)
