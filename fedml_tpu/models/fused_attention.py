"""The causal attention core as fused Pallas kernels: every causal key, a
selection and grouped key heads, or a window of keys.

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

The inputs choose the variant, one algorithm either way:

* ``latent_attention_forward/backward`` (`KERNEL`): every causal key, as
  many key heads as query heads (latent attention);
* ``selected_attention_forward/backward`` (`SELECTED_KERNEL`): a
  selection ``[B, T, T]`` bool (`models.indexed_attention`), handed over as
  int8, and/or ``g = H / Hkv`` query heads a key/value head.  Query head
  ``h`` reads key/value head ``h // g`` through the `BlockSpec` index maps
  (no copy of k or v a query head).  The forward grid is (sequence, key
  head, query block, query head of the group): the ``g`` heads of a key
  head run innermost, so a query block's row of the selection, ``[block,
  T]``, is fetched once a key head, not once a query head.  The backward
  pass takes the selection transposed, ``[keys, queries]`` (XLA makes that
  int8 copy once a step), a key block's row of it fetched each grid step,
  and writes ``dk``/``dv`` a query head, which XLA sums over the group.
  The selection is the whole mask: every score tile is masked by it (the
  diagonal one too), and a key a row did not select adds nothing to it
  (``p`` is 0 there, whatever the running maximum).  PRECONDITION: the
  selection lies inside the causal past and every row selects at least
  one key (the indexer's rows select ``min(t + 1, topk)``); a row that
  selects nothing divides by a zero sum.  Tiles no query selects are
  computed all the same (no skip: at random weights none occurs).
* ``window_attention_forward/backward`` (`WINDOW_KERNEL`): a ``window`` of
  keys (static), query ``t`` seeing ``t - window < s <= t``
  (`models.window_attention`), under the grouped layout and index maps of
  the selected variant and with no selection.  The forward step's loop
  starts at the first key block that holds a key of its first row's
  window, masks by the window the blocks some row sees only in part, runs
  unmasked over the blocks every row sees whole, and masks the diagonal
  block as the other variants do (by the window too where it is shorter
  than a block); the backward step's loop stops at the last query block
  whose rows still see its key block, masking alike.  Tiles outside every
  row's window are neither fetched nor computed: a head visits
  `models.transformer.window_tiles` of them (31 of the 136 causal ones at
  8,192 positions, a window of 512 and blocks of 512), which counts them
  from the loops' own bounds (`window_key_blocks`,
  `window_query_blocks`).  PRECONDITION: a
  window of at least one key, so every row sees its own key; a row that
  sees nothing in a window-masked tile adds nothing there (``p`` is 0),
  as under a selection.

The result and the log-sum-exp carry `checkpoint_name`s (`SAVED`): a
`jax.checkpoint` around the caller that saves those two names (the
block's does) runs the forward kernel once a step, not again for the
backward pass.
"""

from __future__ import annotations

import functools
import math
import operator

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL = "latent_attention"         # its name to `pallas_interpret`
SELECTED_KERNEL = "selected_attention"  # the variant's, likewise
WINDOW_KERNEL = "window_attention"      # and the window's
BLOCK = 512                         # queries and keys a block
# a head's whole k and v (forward) or q, do and dq (backward) stay in
# VMEM: the float32 blocks the pipeline double-buffers, their bfloat16
# copies, dq.  At 8,192 x 256 that is 48 MB forward and 72 MB backward of
# the v5e's 128 MiB, so the kernels ask for 100 MB and `admits` stops at
# that size (a selection's row of `BLOCK` x T int8, double-buffered, adds
# 8 MB at 8,192, 16 MB at the longest admitted T, 16,384 x 128)
MAX_HEAD_ELEMS = 8192 * 256
VMEM_LIMIT = 100 * 1024 * 1024
SAVED = ("attn_core_out", "attn_core_lse")
_MASK = -1e30
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def admits(q, k, v, selected=None, window=None) -> bool:
    """Whether the kernels fit ``q`` [B, T, H, dk], ``k`` [B, T, Hkv, dk],
    ``v`` [B, T, Hkv, dv], ``selected`` (None, or [B, T, T]) and ``window``
    (None, or any number of keys from 1 on, without a selection): float32,
    a whole number of `BLOCK`s, head widths that are multiples of 128
    lanes, ``Hkv`` dividing ``H``, a head that fits in VMEM."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    return (all(x.dtype == jnp.float32 for x in (q, k, v))
            and k.shape[1] == t and t % BLOCK == 0
            and h % k.shape[2] == 0
            and (selected is None or selected.shape == (b, t, t))
            and (window is None or (selected is None and window >= 1))
            and dk % 128 == 0 and dv % 128 == 0
            and t * max(dk, dv) <= MAX_HEAD_ELEMS)


def kernel_name(g, selected=None, window=None) -> str:
    """`WINDOW_KERNEL` under a window, `KERNEL` for every causal key under
    as many key heads as query heads (``g`` query heads a key head: 1),
    else `SELECTED_KERNEL`."""
    if window is not None:
        return WINDOW_KERNEL
    return KERNEL if selected is None and g == 1 else SELECTED_KERNEL


def _int_ops(x):
    """max, min and division of a number >= 0, for a Python int or for a
    traced one (a grid index inside a kernel)."""
    if isinstance(x, int):
        return max, min, operator.floordiv
    return jnp.maximum, jnp.minimum, jax.lax.div


def window_key_blocks(i, block, window):
    """For query block ``i`` under a ``window`` of keys (query ``t`` sees
    ``t - window < s <= t``): ``(first, whole)``, the first key block some
    row of it sees (its first row's oldest key's) and the first from which
    every row sees the whole block, up to the diagonal block ``i``.  The
    forward kernel walks ``first .. i``, the XLA path takes the keys from
    block ``first`` on and `models.transformer.window_tiles` counts ``i + 1
    - first``: the three read their bounds here."""
    mx, mn, div = _int_ops(i)
    first = div(mx(i * block - window + 1, 0), block)
    return first, mn(mx(i + 1 - window // block, first), i)


def window_query_blocks(j, block, window, n):
    """The inverse of `window_key_blocks` for key block ``j`` of ``n``:
    ``(part, end)``, the query blocks ``j + 1 .. part - 1`` see the whole
    of it, ``part .. end - 1`` a part and none from ``end`` on; query block
    ``i > j`` reaches key block ``j`` exactly when its ``first <= j``.  The
    backward kernel walks ``j .. end - 1``."""
    mx, mn, div = _int_ops(j)
    end = mn(div((j + 1) * block - 2 + window, block) + 1, n)
    return mn(mx(j + window // block, j + 1), end), end


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


def _in_window(block, apart, window, transposed):
    """The mask of a tile whose query block lies ``apart`` blocks after
    its key block (0: the diagonal one), query position - key position <
    ``window`` (and, on the diagonal, >= 0): ``[queries, keys]``, or
    ``[keys, queries]`` when ``transposed``."""
    a = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    b = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    lag = apart * block + (b - a if transposed else a - b)
    return (lag >= 0) & (lag < window)


def _forward_kernel(*refs, scale, block, selected, heads_inner, window):
    q_ref, k_ref, v_ref = refs[:3]
    sel_ref = refs[3] if selected else None
    o_ref, lse_ref, kb_ref, vb_ref = refs[3 + selected:]
    i = pl.program_id(2)
    # the key head's first grid step casts what the rest read
    first = (jnp.logical_and(i == 0, pl.program_id(3) == 0) if heads_inner
             else i == 0)

    @pl.when(first)
    def _():
        _cast_rows(k_ref, kb_ref, block)
        _cast_rows(v_ref, vb_ref, block)

    qb = q_ref[...].astype(jnp.bfloat16)

    def pair(j, carry, diagonal, windowed=False):
        m, l, acc = carry
        at = pl.ds(pl.multiple_of(j * block, block), block)
        s = jax.lax.dot_general(qb, kb_ref[at, :], _NT,
                                preferred_element_type=jnp.float32) * scale
        keep = None
        if sel_ref is not None:         # the whole mask, every tile
            keep = sel_ref[:, at] != 0
            s = jnp.where(keep, s, _MASK)
        elif windowed:                  # a row may see none of it
            keep = _in_window(block, i - j, window, False)
            s = jnp.where(keep, s, _MASK)
        elif diagonal:
            s = jnp.where(_below_diagonal(block, False) if window is None
                          or window >= block else
                          _in_window(block, 0, window, False), s, _MASK)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        if keep is not None:            # a row that saw nothing yet
            p = jnp.where(keep, p, 0.0)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = alpha * acc + jnp.dot(p.astype(jnp.bfloat16), vb_ref[at, :],
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    carry = (jnp.full((block, 1), _MASK, jnp.float32),
             jnp.zeros((block, 1), jnp.float32),
             jnp.zeros((block, o_ref.shape[-1]), jnp.float32))
    if window is None:
        carry = jax.lax.fori_loop(0, i, lambda j, c: pair(j, c, False),
                                  carry)
    else:
        # the blocks from ``whole`` on every row of the query block sees
        # whole
        start, whole = window_key_blocks(i, block, window)
        carry = jax.lax.fori_loop(start, whole,
                                  lambda j, c: pair(j, c, False, True),
                                  carry)
        carry = jax.lax.fori_loop(whole, i, lambda j, c: pair(j, c, False),
                                  carry)
    m, l, acc = pair(i, carry, True)
    o_ref[...] = acc / l
    lse_ref[...] = _column_to_row(m + jnp.log(l))


def _backward_kernel(*refs, scale, block, selected, window):
    q_ref, do_ref, k_ref, v_ref, lse_ref, delta_ref = refs[:6]
    sel_ref = refs[6] if selected else None
    dq_ref, dk_ref, dv_ref, qb_ref, dob_ref = refs[6 + selected:]
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        _cast_rows(q_ref, qb_ref, block)
        _cast_rows(do_ref, dob_ref, block)
        dq_ref[...] = jnp.zeros_like(dq_ref)

    kb = k_ref[...].astype(jnp.bfloat16)
    vb = v_ref[...].astype(jnp.bfloat16)

    def pair(i, carry, diagonal, windowed=False):
        dk, dv = carry
        at = pl.ds(pl.multiple_of(i * block, block), block)
        qi, doi = qb_ref[at, :], dob_ref[at, :]
        # [keys, queries]: the rows' log-sum-exp and delta lie along it
        s = jax.lax.dot_general(kb, qi, _NT,
                                preferred_element_type=jnp.float32) * scale
        if sel_ref is not None:         # the selection, transposed
            p = jnp.where(sel_ref[:, at] != 0, jnp.exp(s - lse_ref[:, at]),
                          0.0)
        elif windowed:
            p = jnp.where(_in_window(block, i - j, window, True),
                          jnp.exp(s - lse_ref[:, at]), 0.0)
        else:
            if diagonal:
                s = jnp.where(_below_diagonal(block, True) if window is None
                              or window >= block else
                              _in_window(block, 0, window, True), s, _MASK)
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
    if window is None:
        dk, dv = jax.lax.fori_loop(j + 1, pl.num_programs(2),
                                   lambda i, c: pair(i, c, False), carry)
    else:
        # up to the last query block whose rows see a key of block j;
        # before ``part`` every row sees the whole key block
        part, end = window_query_blocks(j, block, window,
                                        pl.num_programs(2))
        carry = jax.lax.fori_loop(j + 1, part,
                                  lambda i, c: pair(i, c, False), carry)
        dk, dv = jax.lax.fori_loop(part, end,
                                   lambda i, c: pair(i, c, False, True),
                                   carry)
    dk_ref[...] = dk
    dv_ref[...] = dv


# a head's grid steps run in order: its first casts what the rest read
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=VMEM_LIMIT)
# the same with the query heads of a key head innermost
_PARAMS_GROUPED = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=VMEM_LIMIT)


def _head(t, width):
    """A head's whole ``[T, width]``: fetched once a head."""
    return pl.BlockSpec((None, None, t, width), lambda b, h, i: (b, h, 0, 0))


def _rows(block, width):
    """The grid step's ``block`` rows of a head."""
    return pl.BlockSpec((None, None, block, width),
                        lambda b, h, i: (b, h, i, 0))


def _forward(q, k, v, selected, block, interpret, window=None):
    """``q`` [B, H, T, dk], ``k`` [B, Hkv, T, dk], ``v`` [B, Hkv, T, dv],
    ``selected`` None or [B, T, T] bool, ``window`` None or a number of
    keys -> (out [B, H, T, dv], lse [B, H, 1, T])."""
    b, h, t, dk = q.shape
    kv, dv = k.shape[1], v.shape[-1]
    g = h // kv
    args = [q, k, v]
    if kernel_name(g, selected, window) == KERNEL:
        grid, params = (b, h, t // block), _PARAMS
        rows = functools.partial(_rows, block)
        head = functools.partial(_head, t)
        lse = pl.BlockSpec((None, None, 1, block),
                           lambda b, h, i: (b, h, 0, i))
    else:
        # grid (sequence, key head c, query block i, head r of c's
        # group): query head c * g + r
        grid, params = (b, kv, t // block, g), _PARAMS_GROUPED

        def rows(width):
            return pl.BlockSpec((None, None, block, width),
                                lambda b, c, i, r: (b, c * g + r, i, 0))

        def head(width):
            return pl.BlockSpec((None, None, t, width),
                                lambda b, c, i, r: (b, c, 0, 0))
        lse = pl.BlockSpec((None, None, 1, block),
                           lambda b, c, i, r: (b, c * g + r, 0, i))
    in_specs = [rows(dk), head(dk), head(dv)]
    if selected is not None:
        # a query block's row of the selection, fetched as i moves
        in_specs.append(pl.BlockSpec((None, block, t),
                                     lambda b, c, i, r: (b, i, 0)))
        args.append(selected.astype(jnp.int8))
    return pl.pallas_call(
        functools.partial(_forward_kernel, scale=1.0 / math.sqrt(dk),
                          block=block, selected=selected is not None,
                          heads_inner=len(grid) == 4, window=window),
        grid=grid, in_specs=in_specs, out_specs=[rows(dv), lse],
        out_shape=[jax.ShapeDtypeStruct((b, h, t, dv), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, 1, t), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((t, dk), jnp.bfloat16),
                        pltpu.VMEM((t, dv), jnp.bfloat16)],
        compiler_params=params, interpret=interpret,
        name=f"{kernel_name(g, selected, window)}_forward")(*args)


def _backward(q, k, v, lse, delta, do, selected, block, interpret,
              window=None):
    """-> (dq, dk, dv), each as its primal."""
    b, h, t, dk = q.shape
    kv, dv = k.shape[1], v.shape[-1]
    g = h // kv
    row = _head(1, t)       # the head's log-sum-exp, its delta
    if g == 1:
        kv_rows = functools.partial(_rows, block)
    else:
        def kv_rows(width):     # query head h's key block of head h // g
            return pl.BlockSpec((None, None, block, width),
                                lambda b, h, j: (b, h // g, j, 0))
    in_specs = [_head(t, dk), _head(t, dv), kv_rows(dk), kv_rows(dv), row,
                row]
    args = [q, do, k, v, lse, delta]
    if selected is not None:
        # a key block's row of the selection transposed, [keys, queries]
        in_specs.append(pl.BlockSpec((None, block, t),
                                     lambda b, h, j: (b, j, 0)))
        args.append(jnp.swapaxes(selected, 1, 2).astype(jnp.int8))
    dq, dk_h, dv_h = pl.pallas_call(
        functools.partial(_backward_kernel, scale=1.0 / math.sqrt(dk),
                          block=block, selected=selected is not None,
                          window=window),
        grid=(b, h, t // block),
        in_specs=in_specs,
        out_specs=[_head(t, dk), _rows(block, dk), _rows(block, dv)],
        out_shape=[jax.ShapeDtypeStruct((b, h, t, d), jnp.float32)
                   for d in (dk, dk, dv)],
        scratch_shapes=[pltpu.VMEM((t, dk), jnp.bfloat16),
                        pltpu.VMEM((t, dv), jnp.bfloat16)],
        compiler_params=_PARAMS, interpret=interpret,
        name=f"{kernel_name(g, selected, window)}_backward")(*args)
    if g == 1:
        return dq, dk_h, dv_h
    # each query head's share of its key head's gradients, summed
    return (dq,) + tuple(x.reshape(b, kv, g, t, -1).sum(axis=2)
                         for x in (dk_h, dv_h))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _core(q, k, v, selected, block, interpret, window):
    return _forward(q, k, v, selected, block, interpret, window)[0]


def _core_fwd(q, k, v, selected, block, interpret, window):
    out, lse = _forward(q, k, v, selected, block, interpret, window)
    out, lse = (checkpoint_name(x, n) for x, n in zip((out, lse), SAVED))
    return out, (q, k, v, selected, out, lse)


def _core_bwd(block, interpret, window, saved, do):
    q, k, v, selected, out, lse = saved
    delta = jnp.sum(do * out, axis=-1)[:, :, None, :]
    # the selection is integers: no cotangent
    return _backward(q, k, v, lse, delta, do, selected, block,
                     interpret, window) + (None,)


_core.defvjp(_core_fwd, _core_bwd)


def fused_causal_attention(q, k, v, selected=None, *, block: int = BLOCK,
                           interpret: bool = False, window=None):
    """Causal softmax attention of ``q`` [B, T, H, dk], ``k`` [B, T, Hkv,
    dk] and ``v`` [B, T, Hkv, dv] at positions 0..T-1 -> [B, T, H, dv],
    query head ``h`` reading key/value head ``h // (H / Hkv)``, through the
    kernels; ``selected`` [B, T, T] bool, where given, the keys each query
    sees, or ``window``, where given, the number of latest keys it sees
    (the module docstring's preconditions hold).  ``T`` a whole number of
    ``block``s and both widths multiples of 128 (`admits` checks it for
    `BLOCK`)."""
    heads_first = lambda x: x.transpose(0, 2, 1, 3)
    return heads_first(_core(heads_first(q), heads_first(k), heads_first(v),
                             selected, block, interpret, window))
