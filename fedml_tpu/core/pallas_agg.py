"""Pallas TPU kernel: fused robust aggregation (clip + weak-DP + weighted mean).

SURVEY.md §7 step 6 marks the defended aggregation as the framework's Pallas
candidate, and this is it.  The XLA path (the cohort engine's
``transform_update`` hook, fedml_tpu/algorithms/fedavg_robust.py) vmaps
`clip_update` + `add_gaussian_noise` over the cohort, which materialises a
full transformed copy of every client's parameters in HBM ([N, D] written,
then re-read by the weighted mean) — O(3·N·D) HBM traffic.  This kernel
reads each stacked client block ONCE and writes only the [D] aggregate:

    out = Σ_i r_i · (g + s_i · (x_i − g) + σ · n_i)

with r_i the normalized sample weights, s_i the per-client norm-diff clip
scale (min(1, bound/‖x_i−g‖), robust_aggregation.py:38-49), and n_i a
per-client Gaussian stream (weak DP, :51-55) generated in-kernel by a
counter-based PRG (murmur3 finalizer + Box–Muller) — no HBM noise
temporaries.  One VMEM pass per block: O(N·D) reads, O(D) writes.

Clip scales need the GLOBAL update norm across all leaves, so they are a
cheap XLA reduction before the kernel launch (two-phase, like every fused
norm-clip implementation).

Semantics parity: with σ=0 the result equals the XLA compose
``tree_weighted_mean(vmap(clip_update))`` to float tolerance
(tests/test_pallas_agg.py); with σ>0 the noise distribution matches but the
stream differs (murmur counter PRG vs threefry), exactly like the SecAgg
pallas backend (secure/pallas_mask.py).

Compiled or interpreted: `pallas_interpret`, for every in-repo kernel.
"""

from __future__ import annotations

import functools
import logging
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.core.pytree import tree_sub
from fedml_tpu.core.robust import _masked_global_norm, default_is_weight_param

Pytree = Any

log = logging.getLogger(__name__)


def pallas_interpret(kernel: str) -> bool:
    """The ``interpret=`` argument for an in-repo Pallas kernel.  On a TPU
    never interpret: Mosaic compiles it, and a kernel Mosaic refuses is a
    bug to fix.  On the CPU an explicitly requested Pallas backend runs
    through the interpreter (a correctness tool, not a speed).  Any other
    platform is an error, not a silently interpreted kernel.  The decision
    is logged by kernel name (chip_smoke.py asserts "compiled" from it)."""
    platform = jax.default_backend()
    if platform == "tpu":
        log.info("pallas kernel %s: %s", kernel, "compiled")
        return False
    if platform == "cpu":
        log.info("pallas kernel %s: %s", kernel, "interpreted")
        return True
    raise RuntimeError(
        f"pallas kernel {kernel}: platform {platform!r} is neither tpu "
        f"(Mosaic) nor cpu (interpreter); select the xla backend")


_LANES = 128
_MAX_BLOCK_ELEMS = 4096 * 128   # x-block budget: N*rows*128 f32 <= 2 MiB


def _rows_per_block(num_clients: int) -> int:
    rows = max(8, (_MAX_BLOCK_ELEMS // _LANES) // max(num_clients, 1))
    return min(256, rows - rows % 8)


def _murmur_fmix(x: jax.Array) -> jax.Array:
    """murmur3's 32-bit finalizer — a full-avalanche uint32 hash on the
    VPU."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def _gaussian_from_index(idx_h: jax.Array, salt: jax.Array) -> jax.Array:
    """Box–Muller over two counter-PRG uniform streams → N(0,1) f32."""
    bits1 = _murmur_fmix(idx_h ^ salt)
    bits2 = _murmur_fmix(bits1 ^ jnp.uint32(0x27D4EB2F))
    # 24-bit mantissa uniforms in (0,1): never 0, so log is finite.  The
    # shifted values fit in 24 bits, so the uint32->int32 hop is exact
    # (Mosaic has no direct uint32->f32 cast)
    u1 = ((bits1 >> 8).astype(jnp.int32).astype(jnp.float32)
          * (2.0 ** -24) + (2.0 ** -25))
    u2 = (bits2 >> 8).astype(jnp.int32).astype(jnp.float32) * (2.0 ** -24)
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(
        (2.0 * np.pi) * u2)


def _agg_kernel(scales_ref, ratios_ref, seed_ref, x_ref, g_ref, o_ref, *,
                num_clients, noise_std, rows):
    """One [rows, 128] block of one leaf: Σ_i r_i (g + s_i(x_i−g) + σ n_i)."""
    from jax.experimental import pallas as pl

    g = g_ref[:].astype(jnp.float32)
    acc = jnp.zeros_like(g)
    if noise_std:
        block = pl.program_id(0).astype(jnp.uint32)
        r_iota = jax.lax.broadcasted_iota(jnp.uint32, g.shape, 0)
        c_iota = jax.lax.broadcasted_iota(jnp.uint32, g.shape, 1)
        idx = (block * jnp.uint32(rows) + r_iota) * jnp.uint32(_LANES) + c_iota
        idx_h = _murmur_fmix(idx * jnp.uint32(0x9E3779B9) + jnp.uint32(1))
        s0 = _murmur_fmix(seed_ref[0].astype(jnp.uint32))
        s1 = _murmur_fmix(seed_ref[1].astype(jnp.uint32)
                          ^ jnp.uint32(0x5BD1E995))

    def body(i, acc):
        xi = x_ref[i].astype(jnp.float32)
        term = g + scales_ref[i] * (xi - g)
        if noise_std:
            # per-client stream: fold the client index into the round seed
            salt = _murmur_fmix(s0 ^ (s1 + i.astype(jnp.uint32)
                                      * jnp.uint32(0x85EBCA6B)))
            term = term + noise_std * _gaussian_from_index(idx_h, salt)
        return acc + ratios_ref[i] * term

    acc = jax.lax.fori_loop(0, num_clients, body, acc)
    o_ref[:] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("num_clients", "noise_std",
                                             "rows", "interpret"))
def _agg_leaf(x3d, g2d, scales, ratios, seed, *, num_clients, noise_std,
              rows, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    total_rows = x3d.shape[1]
    grid = total_rows // rows
    kernel = functools.partial(_agg_kernel, num_clients=num_clients,
                               noise_std=noise_std, rows=rows)
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # scales[N]
            pl.BlockSpec(memory_space=pltpu.SMEM),   # ratios[N]
            pl.BlockSpec(memory_space=pltpu.SMEM),   # seed[2]
            pl.BlockSpec((num_clients, rows, _LANES), lambda r: (0, r, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rows, _LANES), lambda r: (r, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((rows, _LANES), lambda r: (r, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(g2d.shape, g2d.dtype),
        interpret=interpret,
    )(scales, ratios, seed, x3d, g2d)


def _finalize_kernel(wsum_ref, seed_ref, x_ref, o_ref, *, noise_std, rows):
    """One [rows, 128] block of a shard's flattened fold accumulator:
    ``out = acc / wsum (+ sigma * n)`` — the streamed defended-mean
    finalize as ONE fused pass (division + weak-DP noise, no HBM noise
    temporaries; the clip already happened at fold time, per arrival)."""
    from jax.experimental import pallas as pl

    out = x_ref[:].astype(jnp.float32) / wsum_ref[0]
    if noise_std:
        block = pl.program_id(0).astype(jnp.uint32)
        r_iota = jax.lax.broadcasted_iota(jnp.uint32, out.shape, 0)
        c_iota = jax.lax.broadcasted_iota(jnp.uint32, out.shape, 1)
        idx = (block * jnp.uint32(rows) + r_iota) * jnp.uint32(_LANES) + c_iota
        idx_h = _murmur_fmix(idx * jnp.uint32(0x9E3779B9) + jnp.uint32(1))
        s0 = _murmur_fmix(seed_ref[0].astype(jnp.uint32))
        s1 = _murmur_fmix(seed_ref[1].astype(jnp.uint32)
                          ^ jnp.uint32(0x5BD1E995))
        out = out + noise_std * _gaussian_from_index(idx_h,
                                                     _murmur_fmix(s0 ^ s1))
    o_ref[:] = out.astype(o_ref.dtype)


def make_fused_shard_finalize(*, noise_std: float = 0.0, seed: int = 0,
                              shard_salt: int = 0, interpret: bool = False):
    """Build the fused per-shard finalize of the sharded streaming spine
    (`fedml_tpu.shard_spine.agg`): ``fn(acc_pieces, wsum, ref_pieces,
    step) -> out_pieces`` where the pieces are one shard's slice of the
    fold accumulator, keyed like its wire slice body.

    All float-destined pieces are flattened into ONE padded [rows, 128]
    f32 buffer and ``clip-at-fold + weighted-sum + noise`` completes as a
    single `pallas_call` per shard — the one-kernel-launch-per-shard
    finalize ROADMAP item 2 names.  Integer-destined pieces (step
    counters) take a scalar XLA epilogue inside the same jit (the plain
    path never noises them either).  With ``noise_std=0`` the division
    is elementwise f32 — bit-identical to the XLA compose for f32
    models; sigma>0 matches the noise distribution with a different
    stream (the module's counter PRG vs threefry), exactly like
    `make_fused_robust_aggregate`.

    ``shard_salt`` decorrelates the per-shard noise streams (the fused
    twin of `add_gaussian_noise`'s per-leaf key split);
    ``interpret=True`` runs the same kernel through the Pallas
    interpreter — the CPU/test fallback.

    The returned callable is a fresh ``jax.jit`` (per-instance cache, so
    the jit-once-per-shard pin and the recompile sentry see this
    aggregator's compiles only) with ``_cache_size`` forwarded.
    """
    seed_word = ((int(seed) & 0xFFFFFFFF)
                 ^ (((int(shard_salt) & 0xFFFFFFFF) * 0x9E3779B9)
                    & 0xFFFFFFFF))

    def _finalize(acc_pieces, wsum, ref_pieces, step):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        keys = sorted(acc_pieces)
        fkeys = [k for k in keys if jnp.issubdtype(
            jnp.asarray(ref_pieces[k]).dtype, jnp.floating)]
        out: dict = {}
        # integer-destined pieces: divide + truncate in XLA (tiny; the
        # plain finalize's exact math, noise-free by contract)
        w32 = jnp.asarray(wsum, jnp.float32)
        for k in keys:
            if k not in fkeys:
                a = acc_pieces[k]
                out[k] = (a / w32.astype(a.dtype)).astype(
                    jnp.asarray(ref_pieces[k]).dtype)
        if fkeys:
            sizes = [int(np.prod(acc_pieces[k].shape or (1,)))
                     for k in fkeys]
            flat = jnp.concatenate(
                [acc_pieces[k].astype(jnp.float32).reshape(-1)
                 for k in fkeys])
            total = int(flat.shape[0])
            leaf_rows = -(-total // _LANES)
            rows = max(8, min(256, leaf_rows + (-leaf_rows) % 8))
            pad = (-total) % (rows * _LANES)
            x2d = jnp.pad(flat, (0, pad)).reshape(-1, _LANES)
            seed32 = jnp.stack([jnp.int32(np.int32(np.uint32(seed_word))),
                                jnp.asarray(step, jnp.int32)])
            kernel = functools.partial(_finalize_kernel,
                                       noise_std=float(noise_std),
                                       rows=rows)
            flat_out = pl.pallas_call(
                kernel,
                grid=(x2d.shape[0] // rows,),
                in_specs=[
                    pl.BlockSpec(memory_space=pltpu.SMEM),   # wsum[1]
                    pl.BlockSpec(memory_space=pltpu.SMEM),   # seed[2]
                    pl.BlockSpec((rows, _LANES), lambda r: (r, 0),
                                 memory_space=pltpu.VMEM),
                ],
                out_specs=pl.BlockSpec((rows, _LANES), lambda r: (r, 0),
                                       memory_space=pltpu.VMEM),
                out_shape=jax.ShapeDtypeStruct(x2d.shape, jnp.float32),
                interpret=interpret,
            )(w32.reshape(1), seed32, x2d).reshape(-1)
            off = 0
            for k, size in zip(fkeys, sizes):
                piece = flat_out[off:off + size].reshape(
                    acc_pieces[k].shape)
                out[k] = piece.astype(jnp.asarray(ref_pieces[k]).dtype)
                off += size
        return out

    return jax.jit(_finalize)


def _clip_scales(stacked: Pytree, global_params: Pytree, norm_bound: float,
                 is_weight) -> jax.Array:
    """Per-client min(1, bound/‖x_i−g‖) over weight leaves — the cheap XLA
    reduction phase (phase 1 of 2).  Reuses the same norm helper as the XLA
    clip path (core/robust.py), so 'which leaves count' can never drift
    between the two backends."""
    norms = jax.vmap(
        lambda x: _masked_global_norm(tree_sub(x, global_params), is_weight)
    )(stacked)
    return jnp.minimum(1.0, norm_bound / jnp.maximum(norms, 1e-12))


def make_fused_robust_aggregate(norm_bound: Optional[float] = None,
                                noise_std: float = 0.0,
                                is_weight=default_is_weight_param,
                                interpret: bool = False):
    """Build the fused aggregate for the cohort engine.

    Returns ``aggregate(stacked, weights, global_params, rng)`` (the
    engine passes the extra args when ``aggregate.needs_global`` is set).
    ``norm_bound=None`` disables clipping (s_i = 1); ``noise_std=0``
    disables the in-kernel noise — both defenses off reduces to the plain
    weighted mean.
    """

    def aggregate(stacked, weights, global_params, rng):
        w = jnp.asarray(weights, jnp.float32)
        ratios = w / jnp.maximum(jnp.sum(w), 1e-12)
        n = int(w.shape[0])
        max_clients = (_MAX_BLOCK_ELEMS // _LANES) // 8
        if n > max_clients:
            raise ValueError(
                f"cohort of {n} clients exceeds the fused kernel's VMEM "
                f"budget (max {max_clients}); use the xla defense backend "
                f"for cohorts this large")
        if norm_bound is not None:
            scales = _clip_scales(stacked, global_params, norm_bound,
                                  is_weight)
        else:
            scales = jnp.ones((n,), jnp.float32)
        seed = jax.random.key_data(rng).astype(jnp.uint32)[:2].astype(
            jnp.int32)
        ones = jnp.ones((n,), jnp.float32)

        s_leaves = jax.tree_util.tree_leaves_with_path(stacked)
        g_flat, treedef = jax.tree.flatten(global_params)
        out = []
        for li, ((path, x), g) in enumerate(zip(s_leaves, g_flat)):
            if not jnp.issubdtype(x.dtype, jnp.floating):
                # int leaves (step counters): plain weighted mean, cast back
                acc = jnp.sum(x.astype(jnp.float32)
                              * ratios.reshape((-1,) + (1,) * (x.ndim - 1)),
                              axis=0)
                out.append(acc.astype(x.dtype))
                continue
            # running stats are never clipped (robust_aggregation.py:28-30)
            leaf_scales = scales if is_weight(path) else ones
            flat = x.reshape(n, -1)
            # block rows: the VMEM budget cap, shrunk for small leaves so a
            # 62-element bias pads to one 8x128 tile, not 256x128
            leaf_rows = -(-flat.shape[1] // _LANES)       # ceil(size/128)
            rows = min(_rows_per_block(n), leaf_rows + (-leaf_rows) % 8)
            pad = (-flat.shape[1]) % (rows * _LANES)
            x3d = jnp.pad(flat, ((0, 0), (0, pad))).reshape(n, -1, _LANES)
            g2d = jnp.pad(g.reshape(-1), (0, pad)).reshape(-1, _LANES)
            agg = _agg_leaf(x3d, g2d, leaf_scales, ratios,
                            seed + jnp.int32(li * 31337),
                            num_clients=n, noise_std=float(noise_std),
                            rows=rows, interpret=interpret)
            out.append(agg.reshape(-1)[:g.size].reshape(g.shape))
        return jax.tree.unflatten(treedef, out)

    aggregate.needs_global = True
    return aggregate
