"""zlib's crc32 of a pytree of device arrays, computed on the device.

`TreeCrc` returns exactly `utils.journal.tree_crc(jax.device_get(tree))`:
the crc32 over the leaves' C-order native bytes in `jax.tree.leaves`
order.  Nothing of the tree crosses to the host; one uint32 does.

Without its initial and final XOR, crc32 is linear over GF(2) in the
message's bits, and a little-endian 32-bit word's bits 0..31 are the
reflected CRC's bit order.  So the linear CRC of a chunk of ``kc`` words
is its ``[32 kc]`` bit vector times one constant ``[32 kc, 32]`` 0/1
matrix (`_chunk_matrix`), reduced mod 2: products that are exact on the
matrix unit, 0/1 operands in int8 and sums in int32 (float32 in the
`jnp` products; at most ``32 kc`` <= 2**24).  Then

* a row's chunks combine by Horner's rule, the running CRC carried past
  one more chunk by the 32 x 32 matrix of ``4 kc`` zero bytes
  (`_shift`, zlib's ``crc32_combine``) before the next chunk is added;
* a unit's rows (a leaf, or a segment of packed small leaves) combine
  ``LADDER`` at a time: row ``b`` of a group is carried past the rows
  after it by its own shift matrix, and the ``LADDER`` products are one
  product with the matrices stacked (`_ladder_matrix`); a few levels
  reach one row;
* the units combine the same way, each carried past the bytes after it;
  ``crc32`` of as many zero bytes is the affine part, added last.

Zero words in FRONT of a message change no linear CRC, so a chunk, a
group or a packed segment is padded at its front only.  Where a row is
no whole number of chunks its last chunk reads zeros past the row's end,
which carries the row's CRC past those zeros; its unit's own shift
matrix takes that back (a shift by fewer bytes, or the inverse of one).

A leaf of 32-bit words with two or more axes and at least `PACK_BYTES`
is read in place, as ``[lead, rows, cols]``: the kernel walks its rows
``ROWS`` at a time and each row in chunks of up to `CHUNK` words.  Any
other leaf (small, one axis, or of 8- or 16-bit elements) is copied,
with the small leaves beside it, into one packed segment of little-endian
words, ``[1, rows, 128]`` (ResNet-56's 176 leaves are one segment, one
kernel call).  On a TPU a Pallas kernel (`KERNEL`) computes each row's
CRC: the bit unpack stays in VMEM.  Elsewhere the same products run in
`jnp`.  One jitted program a tree, ``global_crc`` (its module name
starts ``jit_global_crc``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

KERNEL = "global_crc"           # its name to `pallas_interpret`
POLY = 0xEDB88320               # zlib's reflected polynomial
CHUNK = 512                     # words a chunk of a row, at most
ROWS = 512                      # rows a kernel step, at most
LADDER = 128                    # rows combined by one product
PACK_BYTES = 1 << 20            # a leaf below this joins a packed segment
_LANES = 128                    # a packed segment's row, in words
_VMEM_LIMIT = 64 * 1024 * 1024


# -- GF(2) matrices on the host: bit vectors are rows, ``v @ M`` ------------

def _gf2(a, b) -> np.ndarray:
    return ((a.astype(np.int64) @ b.astype(np.int64)) & 1).astype(np.uint8)


def _bits(v: int) -> np.ndarray:
    return ((v >> np.arange(32)) & 1).astype(np.uint8)


def _matrix(images) -> np.ndarray:
    """The matrix that maps bit ``j`` to the int ``images[j]``."""
    return np.stack([_bits(v) for v in images])


# one zero bit through the CRC's register, and its inverse: the register's
# top bit after a zero bit is the bit 0 that fed the polynomial back
_Z = _matrix([POLY] + [1 << (j - 1) for j in range(1, 32)])
_Z_INV = _matrix([(((1 << k) ^ (POLY if k == 31 else 0)) << 1
                   | (k == 31)) & 0xFFFFFFFF for k in range(32)])


@functools.lru_cache(maxsize=None)
def _zero_bits(power: int, inverse: bool) -> np.ndarray:
    """``Z ** (2 ** power)`` (or its inverse)."""
    m = _Z_INV if inverse else _Z
    for _ in range(power):
        m = _gf2(m, m)
    return m


def _shift(nbytes: int) -> np.ndarray:
    """The matrix that carries a linear CRC past ``nbytes`` zero bytes;
    ``nbytes`` < 0 takes it back."""
    out = np.eye(32, dtype=np.uint8)
    n, power = 8 * abs(nbytes), 0
    while n:
        if n & 1:
            out = _gf2(out, _zero_bits(power, nbytes < 0))
        n >>= 1
        power += 1
    return out


def _int(bits) -> int:
    return int(sum(int(b) << j for j, b in enumerate(bits)))


def zeros_crc(nbytes: int) -> int:
    """``zlib.crc32(bytes(nbytes))``: the affine part of any message of
    that length (the register starts all ones and ends inverted)."""
    return _int(_gf2(_bits(0xFFFFFFFF)[None], _shift(nbytes))[0]) \
        ^ 0xFFFFFFFF


@functools.lru_cache(maxsize=None)
def _chunk_matrix(kc: int, stride: int) -> np.ndarray:
    """``[32, kc, 32]``: entry ``[b, t]`` is the linear CRC of a message
    of ``kc`` words ``stride`` words apart whose only set bit is bit ``b``
    of word ``t`` (``31 - b`` zero bits follow it in its word, then
    ``stride * (kc - 1 - t)`` zero words)."""
    planes = _gf2(_bits(POLY)[None], _shift(0))
    for _ in range(31):                     # planes[k]: bit 31 - k
        planes = np.concatenate([planes, _gf2(planes[-1:], _Z)])
    after = planes[::-1][None]              # [words after, b, 32]
    step = _shift(4 * stride)
    while len(after) < kc:
        after = np.concatenate([after, _gf2(after, step)])
        step = _gf2(step, step)
    out = np.ascontiguousarray(after[kc - 1::-1].transpose(1, 0, 2))
    out.setflags(write=False)
    return out


@functools.lru_cache(maxsize=None)
def _ladder_matrix(row_bytes: int) -> np.ndarray:
    """``[LADDER * 32, 32]``: block ``b`` carries row ``b`` of a group of
    ``LADDER`` rows of ``row_bytes`` past the rows after it."""
    step, blocks = _shift(row_bytes), [np.eye(32, dtype=np.uint8)]
    for _ in range(LADDER - 1):
        blocks.append(_gf2(blocks[-1], step))
    out = np.concatenate(blocks[::-1])
    out.setflags(write=False)
    return out


# -- the products, in jnp and in the kernel ---------------------------------

def _mod2(counts):
    return (counts.astype(jnp.int32) & 1).astype(jnp.float32)


def _sum_type(dtype):
    """What 0/1 products of ``dtype`` add up in, exactly."""
    return jnp.int32 if dtype == jnp.int8 else jnp.float32


def _gf2_dot(bits, m):
    """0/1 ``bits`` times the 0/1 matrix ``m``, the sums not yet reduced
    mod 2."""
    return jnp.dot(bits.astype(m.dtype), m,
                   preferred_element_type=_sum_type(m.dtype))


def _chunk_counts(x, g):
    """``x`` [rows, kc] int32 words -> [rows, lanes] float32: each row's
    chunk times ``g`` [32, kc, lanes] (lanes past 32 are zero), one bit
    plane a product, not yet reduced mod 2.  In int8 a 1.51 GB global
    takes 14.4 ms on a TPU v5e (22.9 in bfloat16); the 32 planes
    unrolled take 9.1 ms, but lowering the unrolled kernels costs the
    run's set-up 2 s more."""
    def plane(b, out):
        return out + _gf2_dot(lax.shift_right_logical(x, b) & 1, g[b])
    return lax.fori_loop(
        0, 32, plane,
        jnp.zeros((x.shape[0], g.shape[-1]), _sum_type(g.dtype))
    ).astype(jnp.float32)


def _horner(acc, counts, s):
    """The row's CRC so far carried past one more chunk, plus it."""
    return _gf2_dot(_mod2(acc), s).astype(jnp.float32) + counts


def _pack(bits, axis):
    """0/1 float32 bits -> int32 words, bit ``j`` at index ``j`` of
    ``axis``."""
    at = lax.broadcasted_iota(jnp.int32, bits.shape, axis)
    return jnp.sum(bits.astype(jnp.int32) << at, axis=axis, keepdims=True)


def _crc_kernel(g_ref, s_ref, x_ref, o_ref, acc_ref, *, cols, kc, nc):
    """One grid step (lead, row block, chunk): the chunk's products added
    into the rows' running CRCs by Horner's rule; at the row's last
    chunk the CRCs are written, one int32 a row, as a row of lanes (the
    running CRCs turned on their side once)."""
    from jax.experimental import pallas as pl
    c = pl.program_id(2)
    x = lax.bitcast_convert_type(x_ref[...], jnp.int32)
    if nc * kc != cols:         # the last chunk reads past the row's end
        col = c * kc + lax.broadcasted_iota(jnp.int32, x.shape, 1)
        x = jnp.where(col < cols, x, 0)
    counts = _chunk_counts(x, g_ref)

    @pl.when(c == 0)
    def _():
        acc_ref[...] = counts

    @pl.when(c > 0)
    def _():
        acc_ref[...] = _horner(acc_ref[...], counts, s_ref[...])

    @pl.when(c == nc - 1)
    def _():
        o_ref[...] = _pack(_mod2(acc_ref[...]).T[:32], 0)


def _rows_kernel(x, g, s, kc, interpret):
    """``x`` [lead, rows, cols] 32-bit words -> [lead * rows] int32: each row's
    linear CRC with the row read as ``nc * kc`` words (zeros past its
    end), by `_crc_kernel`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    lead, rows, cols = x.shape
    lanes = g.shape[-1]
    nc = -(-cols // kc)
    tr = rows if rows <= ROWS else ROWS
    out = pl.pallas_call(
        functools.partial(_crc_kernel, cols=cols, kc=kc, nc=nc),
        grid=(lead, -(-rows // tr), nc),
        in_specs=[pl.BlockSpec(g.shape, lambda l, i, c: (0, 0, 0)),
                  pl.BlockSpec(s.shape, lambda l, i, c: (0, 0)),
                  pl.BlockSpec((None, tr, kc), lambda l, i, c: (l, i, c))],
        out_specs=pl.BlockSpec((None, 1, tr), lambda l, i, c: (l, 0, i)),
        out_shape=jax.ShapeDtypeStruct((lead, 1, rows), jnp.int32),
        scratch_shapes=[pltpu.VMEM((tr, lanes), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL,
    )(g, s, x)
    return out.reshape(-1)


def _rows_jnp(x, g, s, kc):
    """`_rows_kernel`'s value by the same products in `jnp`."""
    lead, rows, cols = x.shape
    nc = -(-cols // kc)
    x = lax.bitcast_convert_type(x, jnp.int32).reshape(lead * rows, cols)
    if nc * kc != cols:
        x = jnp.pad(x, ((0, 0), (0, nc * kc - cols)))
    chunks = x.reshape(-1, nc, kc).transpose(1, 0, 2)

    def step(acc, chunk):
        return _horner(acc, _chunk_counts(chunk, g), s), None
    acc, _ = lax.scan(step, jnp.zeros((lead * rows, g.shape[-1]),
                                      jnp.float32), chunks)
    return _pack(_mod2(acc), 1).reshape(-1)


@functools.partial(jax.jit, static_argnames=("kc", "interpret"))
def _rows(x, g, s, *, kc, interpret):
    """Each row's linear CRC (`_rows_kernel`, or `_rows_jnp` where
    ``interpret`` is None); jitted, so the leaves of one shape share one
    trace and one lowering."""
    if interpret is None:
        return _rows_jnp(x, g, s, kc)
    return _rows_kernel(x, g, s, kc, interpret)


def _unpack(words):
    """[n] int32 -> [n, 32] float32 bits."""
    lane = lax.broadcasted_iota(jnp.int32, (words.shape[0], 32), 1)
    return (lax.shift_right_logical(words[:, None], lane) & 1
            ).astype(jnp.float32)


def _front_pad(x, multiple):
    extra = -x.shape[0] % multiple
    if not extra:
        return x
    return jnp.pad(x, ((extra, 0),) + ((0, 0),) * (x.ndim - 1))


def _words(leaves):
    """Packed leaves -> [1, rows, 128] int32: their bytes, in order, as
    little-endian words, the padding in front."""
    if all(leaf.dtype.itemsize == 4 for leaf in leaves):
        words = jnp.concatenate([
            lax.bitcast_convert_type(leaf, jnp.int32).reshape(-1)
            for leaf in leaves])
    else:
        data = jnp.concatenate([
            (leaf.astype(jnp.uint8) if leaf.dtype == jnp.bool_
             else lax.bitcast_convert_type(leaf, jnp.uint8)).reshape(-1)
            for leaf in leaves])
        quads = _front_pad(data, 4).reshape(-1, 4).astype(jnp.uint32)
        words = lax.bitcast_convert_type(
            quads[:, 0] | quads[:, 1] << 8 | quads[:, 2] << 16
            | quads[:, 3] << 24, jnp.int32)
    return _front_pad(words, _LANES).reshape(1, -1, _LANES)


def _in_place(leaf) -> bool:
    return (leaf.ndim >= 2 and leaf.dtype.itemsize == 4
            and leaf.dtype != jnp.bool_ and leaf.size * 4 >= PACK_BYTES)


def _column_major(leaf) -> bool:
    """Whether the leaf's words lie a column after another: the TPU's
    layout of a matrix whose rows are no whole number of 128 lanes and
    whose columns are (GLM's ``[2048, 19360]`` embedding)."""
    fmt = getattr(leaf, "format", None)
    layout = getattr(fmt, "layout", None)
    return leaf.ndim == 2 and getattr(layout, "major_to_minor",
                                      None) == (1, 0)


class _Unit:
    """A leaf read in place, or a run of leaves packed together.  The
    kernel reads the unit as ``[lead, rows, cols]`` words, each row in
    chunks of ``kc``; consecutive words of a row lie ``stride`` words
    apart in the message and consecutive rows ``row_bytes`` apart (a
    column-major matrix is read as its transpose, in place)."""

    def __init__(self, leaves, index):
        self.index = index          # positions in the tree's leaves
        first = leaves[index[0]]
        self.packed = not (len(index) == 1 and _in_place(first))
        self.transposed = not self.packed and _column_major(first)
        self.nbytes = sum(leaves[i].size * leaves[i].dtype.itemsize
                          for i in index)
        if self.packed:
            self.cols, self.stride = _LANES, 1
        elif self.transposed:
            self.cols, self.stride = first.shape[0], first.shape[1]
        else:
            self.cols, self.stride = first.shape[-1], 1
        self.kc = self.cols if self.cols <= CHUNK else CHUNK
        self.row_bytes = 4 if self.transposed else 4 * self.cols
        # bytes the last chunk read past each row's end
        self.overrun = 4 * self.stride * (-self.cols % self.kc)

    def view(self, leaves):
        """The unit's words as ``[lead, rows, cols]``, of any 32-bit
        dtype: a leaf as it lies (the kernel reads its bits as int32)."""
        if self.packed:
            return _words([leaves[i] for i in self.index])
        leaf = leaves[self.index[0]]
        if self.transposed:
            return leaf.T[None]
        return leaf.reshape(-1, *leaf.shape[-2:])


def _plan(leaves):
    """The tree's units, in order: each in-place leaf alone, each run of
    the other leaves between them one packed segment."""
    units, run = [], []
    for i, leaf in enumerate(leaves):
        if leaf.size == 0:
            continue
        if _in_place(leaf):
            if run:
                units.append(_Unit(leaves, run))
                run = []
            units.append(_Unit(leaves, [i]))
        else:
            run.append(i)
    if run:
        units.append(_Unit(leaves, run))
    return units


def _constants(units, dtype, lanes):
    """The program's matrices: each chunk width's pair (`_chunk_matrix`,
    the shift past one chunk), the ladder of each length of row it
    combines, and each unit's final shift (past the bytes after it, less
    its rows' overrun); and each unit's ladder levels, by row length."""
    chunks, ladders, finals, levels = {}, {}, [], []
    after = sum(u.nbytes for u in units)
    for u in units:
        after -= u.nbytes
        key = (u.kc, u.stride)
        if key not in chunks:
            g = np.zeros((32, u.kc, lanes), dtype)
            g[..., :32] = _chunk_matrix(*key)
            step = np.zeros((lanes, lanes), dtype)
            step[:32, :32] = _shift(4 * u.kc * u.stride)
            chunks[key] = (g, step)
        row_bytes, rows, mine = u.row_bytes, -(-u.nbytes // (4 * u.cols)), []
        while rows > 1:
            if row_bytes not in ladders:
                ladders[row_bytes] = _ladder_matrix(row_bytes).astype(
                    np.float32)
            mine.append(row_bytes)
            rows = -(-rows // LADDER)
            row_bytes *= LADDER
        levels.append(mine)
        finals.append(_shift(after - u.overrun).astype(np.float32))
    return (chunks, ladders, finals), levels


class TreeCrc:
    """`tree_crc` of device trees, on the device (the module's
    docstring).  ``kernel`` None: the Pallas kernel on a TPU, the `jnp`
    products elsewhere; True runs the kernel anywhere (interpreted off a
    TPU, `core.pallas_agg.pallas_interpret`).  Keeps a program for each
    kind of tree it is given: structure, shapes, dtypes, placement and
    layout."""

    def __init__(self, kernel: Optional[bool] = None):
        self.kernel = (jax.default_backend() == "tpu" if kernel is None
                       else kernel)
        self._programs = {}

    def dispatch(self, tree) -> jax.Array:
        """Queue the program on ``tree``: a uint32 scalar on the device,
        the CRC once it is there (``int(...)`` reads it)."""
        leaves = jax.tree.leaves(tree)
        key = (jax.tree.structure(tree),
               tuple((x.shape, x.dtype, x.sharding, _column_major(x))
                     for x in leaves))
        run = self._programs.get(key)
        if run is None:
            run = self._programs[key] = _build(leaves, self.kernel)
        return run(leaves)

    def __call__(self, tree) -> int:
        return int(self.dispatch(tree))


def _build(leaves, kernel: bool):
    """The jitted program for trees of ``leaves``' kind, its constants
    placed whole on each of the first leaf's devices."""
    interpret = None
    if kernel:
        from fedml_tpu.core.pallas_agg import pallas_interpret
        interpret = pallas_interpret(KERNEL)
    fn, consts = program(leaves, interpret)
    where = leaves[0].sharding if leaves else None
    if where is not None and not where.is_fully_replicated:
        where = jax.sharding.NamedSharding(where.mesh,
                                           jax.sharding.PartitionSpec())
    jitted, consts = jax.jit(fn), jax.device_put(consts, where)
    return lambda leaves: jitted(leaves, consts)


def program(leaves, interpret: Optional[bool]):
    """``(global_crc, constants)`` for trees of ``leaves``' shapes and
    dtypes: the function of ``(leaves, constants)`` that `TreeCrc` jits,
    and its constant matrices (numpy).  ``interpret`` None computes the
    rows' CRCs by the `jnp` products, else by the kernel (interpreted
    where True)."""
    units = _plan(leaves)
    total = sum(u.nbytes for u in units)
    dtype, lanes = ((np.float32, 32) if interpret is None
                    else (np.int8, _LANES))
    consts, levels = _constants(units, dtype, lanes)

    def global_crc(leaves, consts):
        chunks, ladders, finals = consts
        out = jnp.zeros((1, 32), jnp.float32)
        for u, mine, final in zip(units, levels, finals):
            g, s = chunks[u.kc, u.stride]
            bits = _unpack(_rows(u.view(leaves), g, s, kc=u.kc,
                                 interpret=interpret))
            for row_bytes in mine:
                m = ladders[row_bytes]
                bits = _mod2(jnp.dot(
                    _front_pad(bits, LADDER).reshape(-1, m.shape[0]), m,
                    preferred_element_type=jnp.float32))
            out = out + jnp.dot(bits, final,
                                preferred_element_type=jnp.float32)
        words = _mod2(out).astype(jnp.uint32)[0] \
            << jnp.arange(32, dtype=jnp.uint32)
        return jnp.sum(words, dtype=jnp.uint32) ^ jnp.uint32(
            zeros_crc(total))
    return global_crc, consts
