"""TPU-native secure aggregation: pairwise masking in Z_2^32 under jit.

The reference's TurboAggregate exchanges Lagrange-coded shares through MPI
messages between worker processes (TA_decentralized_worker.py); the finite-
field kernel lives in `fedml_tpu.secure.field` for the cross-silo path.  But
*on-pod*, the TPU-native construction is additive pairwise masking in the
ring Z_2^32 (the practical-SecAgg construction, Bonawitz et al. 2017):

- uint32 wraparound IS the ring arithmetic — no explicit mod anywhere;
- each ordered client pair (i < j) derives a shared mask from a common seed
  (key agreement on the host edge; `jax.random.fold_in` of a cohort key in
  simulation); client i adds it, client j subtracts it;
- the masked cohort sum — a plain `lax.psum`/`sum` in the jit round program
  — cancels every mask exactly, bit for bit.  The server learns only the
  sum, each individual update stays masked.

Quantization float→fixed-point mirrors the role of the reference's
``transform_tensor_to_finite`` step (TA model quantization) with an explicit
clip range and scale.
"""

from __future__ import annotations

import logging
from typing import Any, Optional

import jax
import jax.numpy as jnp

Pytree = Any

log = logging.getLogger(__name__)

# the uint32 ring holds signed fixed-point values in ±2^31; the COHORT SUM
# must stay inside that, not just each update
RING_CAPACITY = 2.0**31


def ring_budget_scale(num_clients: int, clip: float) -> float:
    """Largest power-of-two fixed-point scale whose worst-case cohort sum
    cannot wrap the uint32 ring: ``num_clients * clip * scale < 2^31``.

    Each masked contribution is clipped to ±clip BEFORE quantization, so
    N clients all saturating the clip sum to N*clip — the wrap boundary
    the per-update quantize range used to ignore (every aggregate beyond
    it silently flipped sign).  Deriving the scale from the cohort size
    makes the budget structural instead of a caller obligation."""
    if num_clients < 1:
        raise ValueError(f"num_clients must be >= 1, got {num_clients}")
    if clip <= 0:
        raise ValueError(f"clip must be > 0, got {clip}")
    import math
    scale = 2.0 ** math.floor(math.log2(RING_CAPACITY / (num_clients * clip)))
    while num_clients * clip * scale >= RING_CAPACITY:  # boundary guard
        scale /= 2.0
    if scale < 1.0:
        raise ValueError(
            f"no usable fixed-point scale: {num_clients} clients at "
            f"clip={clip} already exceed the uint32 ring capacity")
    return scale


def validate_ring_budget(num_clients: int, clip: float,
                         scale: float) -> None:
    """Fail loudly when a cohort sum can wrap the ring: the satellite bug
    (ISSUE 11) — quantize's fixed-point range is per-update, but N
    clipped updates sum to N*clip, and a wrapped sum dequantizes to a
    silently-corrupted aggregate (sign-flipped, not noisy)."""
    if num_clients * clip * scale >= RING_CAPACITY:
        raise ValueError(
            f"uint32 ring budget exceeded: num_clients={num_clients} * "
            f"clip={clip} * scale={scale} = "
            f"{num_clients * clip * scale:.3g} >= 2^31 — the cohort sum "
            f"can wrap and corrupt the aggregate.  Lower scale/clip or "
            f"pass scale=None to auto-derive it from the cohort size "
            f"(ring_budget_scale gives {ring_budget_scale(num_clients, clip)})")


def quantize(tree: Pytree, scale: float = 2.0**16,
             clip: float = 2.0**14) -> Pytree:
    """Fixed-point encode float pytree into uint32 ring elements.

    Values are clipped to ±clip then scaled; negatives wrap mod 2^32 (two's
    complement), so additions in uint32 implement signed fixed-point sums as
    long as the true sum stays within ±2^31/scale."""
    def enc(x):
        q = jnp.round(jnp.clip(x, -clip, clip) * scale).astype(jnp.int32)
        return q.astype(jnp.uint32)
    return jax.tree.map(enc, tree)


def dequantize(tree: Pytree, scale: float = 2.0**16) -> Pytree:
    def dec(q):
        return q.astype(jnp.uint32).astype(jnp.int32).astype(jnp.float32) / scale
    return jax.tree.map(dec, tree)


def _pair_key(base_key: jax.Array, i, j) -> jax.Array:
    """Shared key for ordered pair (min,max) — both ends derive the same."""
    lo = jnp.minimum(i, j)
    hi = jnp.maximum(i, j)
    return jax.random.fold_in(jax.random.fold_in(base_key, lo), hi)


def pairwise_masks(base_key: jax.Array, client_idx, num_clients: int,
                   tree: Pytree) -> Pytree:
    """Net mask for one client: +PRG(s_ij) for j>i, −PRG(s_ij) for j<i.

    Σ_i mask_i = 0 in uint32 exactly.  Shapes/dtypes follow ``tree``."""
    def mask_leaf(x):
        def one_pair(j, acc):
            key = _pair_key(base_key, client_idx, j)
            bits = jax.random.bits(key, x.shape, jnp.uint32)
            sign = jnp.where(j > client_idx, jnp.uint32(1),
                             jnp.uint32(0xFFFFFFFF))  # -1 in the ring
            use = (j != client_idx).astype(jnp.uint32)
            return acc + bits * sign * use
        # the zero init inherits client_idx's varying-axis type so the scan
        # carry matches under shard_map (client_idx is axis_index there)
        zero = jnp.zeros(x.shape, jnp.uint32) + \
            jnp.asarray(client_idx).astype(jnp.uint32) * jnp.uint32(0)
        return jax.lax.fori_loop(0, num_clients, one_pair, zero)
    return jax.tree.map(mask_leaf, tree)


class SecureCohortAggregator:
    """Drop-in secure replacement for plain weighted cohort aggregation.

    ``mask_update(update, n_i, client_idx)`` runs on/for each client:
    quantize(update * n_i) + pairwise mask.  ``unmask_sum(masked_sum,
    total_n)`` runs on the server: dequantize / Σn.  Works identically
    whether the sum is a stacked ``sum(axis=0)`` (single chip) or a
    ``lax.psum`` over the cohort mesh axis — masks cancel in either."""

    def __init__(self, num_clients: int, scale: Optional[float] = None,
                 clip: float = 2.0**14, backend: str = "xla"):
        """``backend="pallas"`` fuses quantize+mask into one VMEM pass per
        block with an in-kernel counter PRG (fedml_tpu.secure.pallas_mask)
        — O(D) HBM traffic instead of O(N·D).  The two backends use
        different PRG streams; every client of a cohort must use the same
        one or masks won't cancel.  Note the pallas stream is a 64-bit-keyed
        hash PRG (architecture demo), not the threefry PRF of the XLA path —
        see the pallas_mask module docstring before using it for real
        privacy.

        ``scale=None`` (default) derives the fixed-point scale from the
        cohort size so the worst-case cohort sum (every client's clipped
        contribution at ±clip) cannot wrap the uint32 ring; an explicit
        scale that CAN wrap is rejected at construction instead of
        corrupting an aggregate mid-federation (`validate_ring_budget`)."""
        if backend not in ("xla", "pallas"):
            raise ValueError(f"unknown secagg backend {backend!r}")
        if scale is None:
            scale = ring_budget_scale(num_clients, clip)
            log.debug("secagg: auto-derived scale %g for %d clients at "
                      "clip %g", scale, num_clients, clip)
        else:
            validate_ring_budget(num_clients, clip, scale)
        self.num_clients = num_clients
        self.scale = scale
        self.clip = clip
        self.backend = backend
        if backend == "pallas":
            from fedml_tpu.core.pallas_agg import pallas_interpret
            self._interpret = pallas_interpret("secagg_mask")

    def mask_update(self, update: Pytree, weight, client_idx,
                    round_key: jax.Array) -> Pytree:
        """Quantize(update * weight) + pairwise mask.

        Ring-budget contract: the TRUE cohort sum of weighted values must
        stay within ±2^31/scale or the uint32 sum wraps and dequantizes
        wrong.  Pass NORMALIZED weights (Σweight = 1, as
        ``aggregate_stacked`` does) and the sum is the weighted mean with
        magnitude ≤ clip — safe for any cohort size.  Raw sample counts as
        weights put the budget on the caller (server divides by Σn)."""
        if self.backend == "pallas":
            from fedml_tpu.secure.pallas_mask import fused_quantize_mask
            return fused_quantize_mask(
                update, weight, client_idx, round_key, self.num_clients,
                self.scale, self.clip, interpret=self._interpret)
        weighted = jax.tree.map(
            lambda x: x * jnp.asarray(weight, x.dtype), update)
        q = quantize(weighted, self.scale, self.clip)
        masks = pairwise_masks(round_key, jnp.asarray(client_idx),
                               self.num_clients, q)
        return jax.tree.map(jnp.add, q, masks)

    def unmask_sum(self, masked_sum: Pytree, total_weight=1.0) -> Pytree:
        deq = dequantize(masked_sum, self.scale)
        return jax.tree.map(
            lambda x: x / jnp.maximum(
                jnp.asarray(total_weight, jnp.float32), 1e-12), deq)

    def aggregate_stacked(self, updates: Pytree, num_samples: jax.Array,
                          round_key: jax.Array) -> Pytree:
        """Single-chip simulation path: updates' leaves are [C, ...].

        Weights are normalized BEFORE masking so each client contributes
        w_i/Σw · update — the ring sum is the weighted mean itself, bounded
        by max|update| ≤ clip, which cannot wrap uint32 regardless of
        cohort size or sample counts."""
        total = jnp.maximum(jnp.sum(num_samples), 1e-12)
        w_norm = num_samples / total
        def per_client(c):
            upd = jax.tree.map(lambda x: x[c], updates)
            return self.mask_update(upd, w_norm[c], c, round_key)
        clients = jnp.arange(self.num_clients)
        if self.backend == "pallas":
            # Mosaic refuses to block the kernel's SMEM seeds/signs over
            # a vmapped client axis — walk the clients instead
            masked = jax.lax.map(per_client, clients)
        else:
            masked = jax.vmap(per_client)(clients)
        summed = jax.tree.map(lambda x: jnp.sum(x, axis=0, dtype=jnp.uint32),
                              masked)
        return self.unmask_sum(summed, 1.0)
