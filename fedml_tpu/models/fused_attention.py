"""The causal attention core as fused Pallas kernels (ISSUE 38).

`models.transformer.causal_blocked_attention` hands a sequence to these on
a TPU where `admits` says they fit; everywhere else its XLA blocks run.
The mathematics is that path's at XLA's default precision on the chip: the
two operands of every product are rounded to bfloat16 at the product and
accumulated in float32; q, k, v, the result and the three gradients enter
and leave as float32; the scale, the mask, the running maximum and sum,
the ``exp`` and the log-sum-exp kept for the backward pass are float32.

Two kernels, a `jax.custom_vjp` between them, on ``[B, heads, T, width]``
arrays (every kernel's FIRST result has that form: the benchmark's reader
finds the attention group by it):

* forward, one grid step a (sequence, head, query block): the head's keys
  and values are cast to bfloat16 into VMEM once, at the head's first
  query block, and the step walks the key blocks up to its diagonal with
  an online softmax; blocks above the diagonal are neither fetched nor
  computed and only the diagonal block is masked.  Two products a
  (query block, key block) pair.  It also returns the log-sum-exp of every
  row, as a row ``[B, heads, 1, T]``.
* backward, one grid step a (sequence, head, key block): the head's
  queries and result gradients are cast into VMEM once, the head's ``dq``
  (``T x width`` float32) stays in VMEM as the step's resident output, and
  the step walks the query blocks from its diagonal down with the scores
  TRANSPOSED, ``[keys, queries]``, so that the saved log-sum-exp and the
  row sums ``delta = sum(do * o)`` are rows beside them and only one of
  the five products (``dq``) contracts over the leading axis.  Nothing of
  the forward pass is computed twice beyond the scores themselves: seven
  products a pair in all, against the nine of a ``dkv`` + ``dq`` pair of
  kernels.

The result and the log-sum-exp carry `checkpoint_name`s (`SAVED`): a
`jax.checkpoint` around the caller that saves those two names (the
latent-attention block's does) runs the forward kernel once a step, not
again for the backward pass.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL = "latent_attention"         # its name to `pallas_interpret`
BLOCK = 512                         # queries and keys a block
# a head's whole k and v (forward) or q, do and dq (backward) stay in
# VMEM: the float32 blocks the pipeline double-buffers, their bfloat16
# copies, dq.  At 8,192 x 256 that is 48 MB forward and 72 MB backward of
# the v5e's 128 MiB, so the kernels ask for 100 MB and `admits` stops at
# that size
MAX_HEAD_ELEMS = 8192 * 256
VMEM_LIMIT = 100 * 1024 * 1024
SAVED = ("attn_core_out", "attn_core_lse")
_MASK = -1e30
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def admits(q, k, v) -> bool:
    """Whether the kernels fit ``q``/``k`` [B, T, H, dk], ``v``
    [B, T, H, dv]: float32, a whole number of `BLOCK`s, head widths that
    are multiples of 128 lanes, a head that fits in VMEM."""
    t, dk, dv = q.shape[1], q.shape[-1], v.shape[-1]
    return (all(x.dtype == jnp.float32 for x in (q, k, v))
            and k.shape[1] == t and t % BLOCK == 0
            and dk % 128 == 0 and dv % 128 == 0
            and t * max(dk, dv) <= MAX_HEAD_ELEMS)


def _cast_rows(src_ref, dst_ref, block):
    """``dst = bfloat16(src)``, a block of rows at a time."""
    def body(c, _):
        at = pl.ds(pl.multiple_of(c * block, block), block)
        dst_ref[at, :] = src_ref[at, :].astype(jnp.bfloat16)
        return 0
    jax.lax.fori_loop(0, src_ref.shape[0] // block, body, 0)


def _column_to_row(col):
    """``[n, 1] -> [1, n]`` without a transpose: each 128 rows are spread
    over the diagonal of a 128 x 128 tile and summed down the sublanes."""
    eye = (jax.lax.broadcasted_iota(jnp.int32, (128, 128), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (128, 128), 1))
    return jnp.concatenate(
        [jnp.sum(jnp.where(eye, col[c:c + 128], 0.0), axis=0, keepdims=True)
         for c in range(0, col.shape[0], 128)], axis=1)


def _below_diagonal(block, transposed):
    """The diagonal block's mask, key position <= query position:
    ``[queries, keys]``, or ``[keys, queries]`` when ``transposed``."""
    a = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    b = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    return a <= b if transposed else b <= a


def _forward_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, kb_ref, vb_ref, *,
                    scale, block):
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _():
        _cast_rows(k_ref, kb_ref, block)
        _cast_rows(v_ref, vb_ref, block)

    qb = q_ref[...].astype(jnp.bfloat16)

    def pair(j, carry, diagonal):
        m, l, acc = carry
        at = pl.ds(pl.multiple_of(j * block, block), block)
        s = jax.lax.dot_general(qb, kb_ref[at, :], _NT,
                                preferred_element_type=jnp.float32) * scale
        if diagonal:
            s = jnp.where(_below_diagonal(block, False), s, _MASK)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = alpha * acc + jnp.dot(p.astype(jnp.bfloat16), vb_ref[at, :],
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    carry = (jnp.full((block, 1), _MASK, jnp.float32),
             jnp.zeros((block, 1), jnp.float32),
             jnp.zeros((block, o_ref.shape[-1]), jnp.float32))
    carry = jax.lax.fori_loop(0, i, lambda j, c: pair(j, c, False), carry)
    m, l, acc = pair(i, carry, True)
    o_ref[...] = acc / l
    lse_ref[...] = _column_to_row(m + jnp.log(l))


def _backward_kernel(q_ref, do_ref, k_ref, v_ref, lse_ref, delta_ref,
                     dq_ref, dk_ref, dv_ref, qb_ref, dob_ref, *,
                     scale, block):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        _cast_rows(q_ref, qb_ref, block)
        _cast_rows(do_ref, dob_ref, block)
        dq_ref[...] = jnp.zeros_like(dq_ref)

    kb = k_ref[...].astype(jnp.bfloat16)
    vb = v_ref[...].astype(jnp.bfloat16)

    def pair(i, carry, diagonal):
        dk, dv = carry
        at = pl.ds(pl.multiple_of(i * block, block), block)
        qi, doi = qb_ref[at, :], dob_ref[at, :]
        # [keys, queries]: the rows' log-sum-exp and delta lie along it
        s = jax.lax.dot_general(kb, qi, _NT,
                                preferred_element_type=jnp.float32) * scale
        if diagonal:
            s = jnp.where(_below_diagonal(block, True), s, _MASK)
        p = jnp.exp(s - lse_ref[:, at])
        dv = dv + jnp.dot(p.astype(jnp.bfloat16), doi,
                          preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(vb, doi, _NT,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[:, at]) * scale).astype(jnp.bfloat16)
        dk = dk + jnp.dot(ds, qi, preferred_element_type=jnp.float32)
        dq_ref[at, :] += jax.lax.dot_general(
            ds, kb, _TN, preferred_element_type=jnp.float32)
        return dk, dv

    carry = pair(j, (jnp.zeros(dk_ref.shape, jnp.float32),
                     jnp.zeros(dv_ref.shape, jnp.float32)), True)
    dk, dv = jax.lax.fori_loop(j + 1, pl.num_programs(2),
                               lambda i, c: pair(i, c, False), carry)
    dk_ref[...] = dk
    dv_ref[...] = dv


# a head's grid steps run in order: its first casts what the rest read
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=VMEM_LIMIT)


def _head(t, width):
    """A head's whole ``[T, width]``: fetched once a head."""
    return pl.BlockSpec((None, None, t, width), lambda b, h, i: (b, h, 0, 0))


def _rows(block, width):
    """The grid step's ``block`` rows of a head."""
    return pl.BlockSpec((None, None, block, width),
                        lambda b, h, i: (b, h, i, 0))


def _forward(q, k, v, block, interpret):
    """``q``/``k`` [B, H, T, dk], ``v`` [B, H, T, dv] ->
    (out [B, H, T, dv], lse [B, H, 1, T])."""
    b, h, t, dk = q.shape
    dv = v.shape[-1]
    return pl.pallas_call(
        functools.partial(_forward_kernel, scale=1.0 / math.sqrt(dk),
                          block=block),
        grid=(b, h, t // block),
        in_specs=[_rows(block, dk), _head(t, dk), _head(t, dv)],
        out_specs=[_rows(block, dv),
                   pl.BlockSpec((None, None, 1, block),
                                lambda b, h, i: (b, h, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((b, h, t, dv), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, 1, t), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((t, dk), jnp.bfloat16),
                        pltpu.VMEM((t, dv), jnp.bfloat16)],
        compiler_params=_PARAMS, interpret=interpret,
        name="latent_attention_forward")(q, k, v)


def _backward(q, k, v, lse, delta, do, block, interpret):
    """-> (dq, dk, dv), each as its primal."""
    b, h, t, dk = q.shape
    dv = v.shape[-1]
    row = _head(1, t)       # the head's log-sum-exp, its delta
    return pl.pallas_call(
        functools.partial(_backward_kernel, scale=1.0 / math.sqrt(dk),
                          block=block),
        grid=(b, h, t // block),
        in_specs=[_head(t, dk), _head(t, dv), _rows(block, dk),
                  _rows(block, dv), row, row],
        out_specs=[_head(t, dk), _rows(block, dk), _rows(block, dv)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32)
                   for x in (q, k, v)],
        scratch_shapes=[pltpu.VMEM((t, dk), jnp.bfloat16),
                        pltpu.VMEM((t, dv), jnp.bfloat16)],
        compiler_params=_PARAMS, interpret=interpret,
        name="latent_attention_backward")(q, do, k, v, lse, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _core(q, k, v, block, interpret):
    return _forward(q, k, v, block, interpret)[0]


def _core_fwd(q, k, v, block, interpret):
    out, lse = _forward(q, k, v, block, interpret)
    out, lse = (checkpoint_name(x, n) for x, n in zip((out, lse), SAVED))
    return out, (q, k, v, out, lse)


def _core_bwd(block, interpret, saved, do):
    q, k, v, out, lse = saved
    delta = jnp.sum(do * out, axis=-1)[:, :, None, :]
    return _backward(q, k, v, lse, delta, do, block, interpret)


_core.defvjp(_core_fwd, _core_bwd)


def fused_causal_attention(q, k, v, *, block: int = BLOCK,
                           interpret: bool = False):
    """Causal softmax attention of ``q``/``k`` [B, T, H, dk] and ``v``
    [B, T, H, dv] at positions 0..T-1 -> [B, T, H, dv], through the
    kernels.  ``T`` a whole number of ``block``s and both widths multiples
    of 128 (`admits` checks it for `BLOCK`)."""
    heads_first = lambda x: x.transpose(0, 2, 1, 3)
    return heads_first(_core(heads_first(q), heads_first(k), heads_first(v),
                             block, interpret))
