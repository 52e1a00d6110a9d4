"""Pallas TPU kernel: fused quantize + pairwise-mask for secure aggregation.

The hot op of on-pod SecAgg (`fedml_tpu.secure.secagg`) is per-client
``quantize(weight * update) + Σ_j ±PRG(s_ij)``.  The XLA path materialises
N-1 leaf-sized threefry mask arrays per client and sums them — O(N·D) HBM
traffic per client just for masks.  This kernel does the whole thing in ONE
VMEM pass per block: load the f32 block once, quantize on the VPU, generate
each pair's mask stream with a counter-based in-kernel PRG (murmur3
finalizer over the global element index — no HBM temporaries, no sequential
PRNG state), accumulate in uint32, and store the masked block.  HBM traffic
drops from O(N·D) to O(D).

Correctness requirement: pair (i, j) must generate IDENTICAL bits on both
ends so masks cancel in the cohort sum.  The PRG is ``hash(pair_seed,
element_index)`` with the symmetric pair seed from `derive_pair_seeds` —
stateless, so client i's +bits equal client j's −bits exactly by
construction, on any backend.

Security note: this stream is a murmur3-based counter PRG keyed by the
64-bit pair secret — weaker than the XLA path's threefry (a cryptographic
PRF with a 128-bit-state key schedule).  It demonstrates the fused-kernel
architecture; a production deployment should swap ``_murmur_fmix`` for a
few rounds of a real block cipher (the kernel structure is unchanged).

CPU/test fallback: ``interpret=True`` runs the same kernel semantics through
the Pallas interpreter (tests assert exact ring cancellation there); real
speed needs the TPU backend.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

# murmur3's finalizer, shared with the aggregation kernels: a counter-based
# PRG (hash(seed, index)) needs no sequential state, so the two ends of a
# pair trivially generate identical streams
from fedml_tpu.core.pallas_agg import _murmur_fmix

Pytree = Any

_LANES = 128
_BLOCK_ROWS = 256          # 256x128 f32 block = 128 KiB in VMEM


def derive_pair_seeds(round_key: jax.Array, client_idx,
                      num_clients: int) -> jax.Array:
    """int32[num_clients, 2] symmetric pair seeds — BOTH words of the
    threefry pair key, so the in-kernel counter PRG is keyed with the full
    64 bits of pair secret; both ends derive the same values (fold_in of
    the sorted pair, matching secagg._pair_key)."""
    def one(j):
        lo = jnp.minimum(client_idx, j)
        hi = jnp.maximum(client_idx, j)
        key = jax.random.fold_in(jax.random.fold_in(round_key, lo), hi)
        return jax.random.key_data(key).astype(jnp.uint32)[:2].astype(
            jnp.int32)
    return jax.vmap(one)(jnp.arange(num_clients))


def _mask_kernel(seeds_ref, signs_ref, x_ref, o_ref, *, num_clients,
                 scale, clip):
    """One [BLOCK_ROWS, 128] block: quantize + accumulate all pair masks."""
    from jax.experimental import pallas as pl

    q = jnp.round(jnp.clip(x_ref[:], -clip, clip) * scale)
    acc = q.astype(jnp.int32).astype(jnp.uint32)
    # global element index (stable across the grid -> both pair ends agree)
    block = pl.program_id(0).astype(jnp.uint32)
    rows = jax.lax.broadcasted_iota(jnp.uint32, acc.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, acc.shape, 1)
    idx = (block * jnp.uint32(_BLOCK_ROWS) + rows) * jnp.uint32(_LANES) + cols
    idx_h = _murmur_fmix(idx * jnp.uint32(0x9E3779B9) + jnp.uint32(1))

    def body(j, acc):
        s0 = seeds_ref[j, 0].astype(jnp.uint32)
        s1 = seeds_ref[j, 1].astype(jnp.uint32)
        # both 32-bit key words enter the stream independently: full 64-bit
        # pair secret keys the counter PRG
        bits = _murmur_fmix(idx_h ^ _murmur_fmix(s0)
                            ^ _murmur_fmix(s1 ^ jnp.uint32(0x5BD1E995)))
        return acc + bits * signs_ref[j]

    acc = jax.lax.fori_loop(0, num_clients, body, acc)
    o_ref[:] = acc


@functools.partial(jax.jit, static_argnames=("num_clients", "scale", "clip",
                                             "interpret"))
def _masked_flat(x2d, seeds, signs, *, num_clients, scale, clip, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = x2d.shape[0]
    grid = rows // _BLOCK_ROWS
    kernel = functools.partial(_mask_kernel, num_clients=num_clients,
                               scale=scale, clip=clip)
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # seeds[N]
            pl.BlockSpec(memory_space=pltpu.SMEM),   # signs[N]
            pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, jnp.uint32),
        interpret=interpret,
    )(seeds, signs, x2d)


def fused_quantize_mask(tree: Pytree, weight, client_idx,
                        round_key: jax.Array, num_clients: int,
                        scale: float = 2.0**16, clip: float = 2.0**14,
                        interpret: bool = False) -> Pytree:
    """Pallas-fused equivalent of
    ``secagg.quantize(weight*tree) + secagg.pairwise_masks(...)``.

    Same ring semantics (uint32 wraparound, +PRG for j>i, -PRG for j<i) but
    a DIFFERENT PRG stream than the XLA path — all clients of a cohort must
    use the same path for masks to cancel.
    """
    client_idx = jnp.asarray(client_idx)
    seeds = derive_pair_seeds(round_key, client_idx, num_clients)
    idx = jnp.arange(num_clients)
    signs = jnp.where(idx == client_idx, jnp.uint32(0),
                      jnp.where(idx > client_idx, jnp.uint32(1),
                                jnp.uint32(0xFFFFFFFF)))

    def leaf(leaf_id, x):
        w = jnp.asarray(weight, x.dtype)
        flat = (x * w).reshape(-1)
        block = _BLOCK_ROWS * _LANES
        pad = (-flat.size) % block
        x2d = jnp.pad(flat, (0, pad)).reshape(-1, _LANES)
        # distinct PRG stream per leaf (same-shape leaves must not share
        # masks); the offset is leaf-position-deterministic, so every
        # client derives the same per-leaf seeds and cancellation holds
        out = _masked_flat(x2d, seeds + jnp.int32(leaf_id * 31337), signs,
                           num_clients=num_clients,
                           scale=float(scale), clip=float(clip),
                           interpret=interpret)
        return out.reshape(-1)[:flat.size].reshape(x.shape)

    leaves, treedef = jax.tree.flatten(tree)
    return jax.tree.unflatten(
        treedef, [leaf(i, x) for i, x in enumerate(leaves)])
