"""Streaming O(1)-memory defended aggregation (ROADMAP item 2).

The stack-then-reduce path (`robust/defense.make_defended_aggregate`
over a ``[cohort, ...]`` host buffer) makes server peak RSS linear in
cohort size — the scaling wall between today's ~8-silo cross-silo path
and the 1k–100k sampled clients of the cross-device north star.
Following "Performance Improvement of FL Server using Smart NIC"
(arXiv 2307.06561), aggregation belongs in the *receive path*: this
module folds each admitted upload into O(model) running state at
arrival, so the barrier-close does one finalize instead of an O(cohort)
reduction, and nothing model-sized is ever held per silo.

Two regimes, chosen by the aggregation rule:

* ``mean`` — an exact streaming fold.  One jit (donate-in-place on the
  accumulator) computes ``acc += clip(update, reference) * w`` per
  arrival; ``finalize`` divides by the folded weight total and adds the
  per-round weak-DP noise.  The fold is arithmetically the SAME
  sequential reduction the stack path's `lax.scan` mean runs over the
  cohort axis, so when uploads fold in slot order the two modes agree
  **bit for bit** (weight-0 slots — dropped stragglers, quarantined or
  rejected silos — contribute an exact ``+0.0`` to the stack scan and
  are simply never folded here).  Memory: O(model), flat in cohort.

* ``krum / coordinate_median / trimmed_mean / multi_krum /
  geometric_median`` — order statistics need a population, so exact
  streaming is impossible.  The trade (documented, bounded): a
  **reservoir** of ``reservoir_k`` slots (Vitter's Algorithm R, seeded)
  holds a uniform sample of the round's admitted uploads; ``finalize``
  runs the unchanged `core/byzantine.py` rule over the static
  ``[K, ...]`` reservoir via `make_defended_aggregate`.  For cohorts
  ``<= K`` the rule sees every upload (exact up to slot order); beyond
  that it sees a uniform K-subsample — the breakdown point degrades
  from f/N to f/K in expectation, so size K to the assumed adversary
  count, not the cohort.  Memory: O(K * model), flat in cohort.

The same object serves three sites: the sync server's admission-accept
path, the async server's delta buffer (``kind="delta"``: clip reference
is zeros), and the edge aggregators of the live multi-level topology
(`algorithms/hierarchical.EdgeAggregatorActor`), which fold their silos'
uploads locally and ship one pre-reduced ``(mean, weight, count)`` edge
to the root.
"""

from __future__ import annotations

import logging
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.core.pytree import acc_dtype
from fedml_tpu.core.robust import add_gaussian_noise, clip_update
from fedml_tpu.obs import telemetry, trace

log = logging.getLogger(__name__)

STREAM_MODES = ("stream", "stack")


def zeros_acc_like(reference):
    """A fresh fold accumulator for ``reference``: same shapes, leaves
    in `acc_dtype` (floats accumulate in their own dtype, ints in f32).
    Shared with the sharded spine (`fedml_tpu.shard_spine.agg`) — the
    accumulator-dtype contract must stay one definition or the
    sharded-vs-replicated bit-identity pins break."""
    return jax.tree.map(
        lambda r: jnp.zeros(jnp.shape(r), acc_dtype(jnp.asarray(r).dtype)),
        reference)


class StreamingAggregator:
    """O(model)-memory fold-at-arrival defended aggregation.

    Round protocol::

        agg.reset(global_params)          # round open (broadcast)
        agg.fold(upload, num_samples)     # per admitted upload, at arrival
        new_global = agg.finalize(step)   # barrier close

    ``template``: the global params at construction — fixes every shape
    so the fold jit compiles exactly once (``_cache_size() == 1`` across
    rounds is the acceptance pin; register with a `RecompileSentry` via
    ``sentry=``).  ``kind="params"`` clips each upload against the
    round's reference global (the sync servers' semantics);
    ``kind="delta"`` clips against zeros and pads the reservoir with
    zero deltas (the async server's semantics).

    The accumulator buffer is donated to each fold so XLA reuses it in
    place — O(model) steady state with zero per-fold allocation, on every
    backend (the CPU tier runs the same donated programs the chip runs).

    ``device``: a `fedml_tpu.obs.device.DeviceRecorder`; when set, the
    hot fold/finalize jits run behind the observatory's wrappers — each
    compile lands in the round's named compile ledger and every call's
    cost-analysis FLOPs feed the live MFU gauge.  The wrappers forward
    ``_cache_size``, so the jit-once pin holds unchanged.
    """

    def __init__(self, template, *, method: str = "mean",
                 kind: str = "params", norm_clip: float = 0.0,
                 noise_std: float = 0.0, seed: int = 0,
                 reservoir_k: int = 64, trim_frac: float = 0.1,
                 byz_f: int = 0, krum_m: int = 1, gm_iters: int = 8,
                 gm_eps: float = 1e-6, sentry=None,
                 device=None):
        from fedml_tpu.robust.defense import (ROBUST_AGG_METHODS,
                                              make_defended_aggregate)
        if method not in ROBUST_AGG_METHODS:
            raise ValueError(f"unknown streaming aggregation method "
                             f"{method!r}; available: {ROBUST_AGG_METHODS}")
        if kind not in ("params", "delta"):
            raise ValueError(f"kind must be 'params' or 'delta', got {kind!r}")
        if reservoir_k < 1:
            raise ValueError(f"reservoir_k must be >= 1, got {reservoir_k}")
        if norm_clip < 0 or noise_std < 0:
            raise ValueError(f"norm_clip/noise_std must be >= 0, got "
                             f"{norm_clip}/{noise_std}")
        self.method = method
        self.kind = kind
        self.norm_clip = norm_clip
        self.noise_std = noise_std
        self.reservoir_k = reservoir_k
        # the template's structure, kept for state_dict/load_state_dict:
        # a crash-resumed fold rebuilds its trees from flat snapshot
        # leaves without the caller re-supplying the round reference
        self._treedef = jax.tree.structure(template)
        # defended = the label contract obs/perf.py documents: the
        # finalize span is "defended_aggregate" only when a defense
        # actually runs (clip, noise, or a Byzantine rule)
        self.defended = (method != "mean" or norm_clip > 0 or noise_std > 0)
        reg = telemetry.get_registry()
        self._c_folds = reg.counter("fedml_stream_folds_total")
        self._c_evict = reg.counter("fedml_stream_evictions_total")
        self._g_reservoir = reg.gauge("fedml_stream_reservoir_fill_total")
        self._h_finalize = reg.histogram("fedml_stream_finalize_seconds")

        # per-round state
        self._reference = None          # device global (clip reference)
        self._acc = None                # running weighted sum (mean mode)
        self._wsum = None               # running weight total (device f32)
        self.count = 0                  # uploads folded this round
        self.weight_total = 0.0         # host f64 fold-order weight sum:
        #                                 readable AFTER finalize (the
        #                                 device _wsum is dropped there)
        #                                 — the edge frame's num_samples
        #                                 and the health observatory
        #                                 both read it
        self._seen = 0                  # reservoir: uploads offered
        self._res_leaves: Optional[list] = None   # [K, ...] host buffers
        self._res_def = None
        self._res_weights: Optional[np.ndarray] = None
        self._res_rng = np.random.RandomState(seed)

        if method == "mean":
            def _fold(acc, wsum, upload, weight, reference):
                if norm_clip > 0:
                    upload = clip_update(upload, reference, norm_clip)
                weight = jnp.asarray(weight, jnp.float32)
                acc = jax.tree.map(
                    lambda a, u: a + u.astype(a.dtype)
                    * weight.astype(a.dtype), acc, upload)
                return acc, wsum + weight

            def _finalize(acc, wsum, reference, step):
                out = jax.tree.map(
                    lambda a, r: (a / wsum.astype(a.dtype)).astype(r.dtype),
                    acc, reference)
                if noise_std > 0:
                    key = jax.random.fold_in(jax.random.key(seed),
                                             jnp.asarray(step, jnp.uint32))
                    out = add_gaussian_noise(out, key, noise_std)
                return out

            def _fold_wave(acc, wsum, stacked, weights, reference):
                # the WAVE fold (cross-device engine): a sequential
                # lax.scan over the wave's slot axis running EXACTLY the
                # per-upload fold body per slot — so a wave-chunked
                # round, a single-wave round, and per-upload folds of
                # the same updates in slot order all land bit-identical
                # accumulators (weight-0 padded slots contribute an
                # exact +0.0, the stack-scan convention)
                def body(carry, xs):
                    a, ws = carry
                    upload, weight = xs
                    if norm_clip > 0:
                        upload = clip_update(upload, reference, norm_clip)
                    a = jax.tree.map(
                        lambda ai, ui: ai + ui.astype(ai.dtype)
                        * weight.astype(ai.dtype), a, upload)
                    return (a, ws + weight), None
                (acc, wsum), _ = jax.lax.scan(
                    body, (acc, wsum), (stacked, weights))
                return acc, wsum

            def _fold_sum(acc, wsum, wave_sum, wave_weight):
                # a wave that left the device as its own slot-order sum
                # (`fold_sum`): one add a leaf
                return (jax.tree.map(jnp.add, acc, wave_sum),
                        wsum + wave_weight)

            self._fold_sum_fn = jax.jit(_fold_sum, donate_argnums=(0, 1))
            self._fold_fn = jax.jit(
                _fold, donate_argnums=(0, 1))
            self._fold_wave_fn = jax.jit(
                _fold_wave, donate_argnums=(0, 1))
            self._finalize_fn = jax.jit(_finalize)
            if device is not None:
                # per-arrival hot path: every fold call feeds the
                # compile ledger + FLOPs accounting (wrapper forwards
                # the _cache_size probe, so the jit-once pin holds).
                # Signatures note under the SENTRY's registration name
                # (stream_agg[...], the aggregator itself below) so a
                # firing verdict can name the shape that changed; the
                # mean finalize has a different arg shape and is not the
                # sentry-monitored cache, so it feeds no signatures.
                self._fold_fn = device.instrument(
                    f"stream_fold[{method}]", self._fold_fn, sentry=sentry,
                    sentry_name=f"stream_agg[{method}]")
                self._fold_wave_fn = device.instrument(
                    f"stream_fold_wave[{method}]", self._fold_wave_fn,
                    sentry=sentry, sentry_name=f"stream_agg[{method}]")
                self._finalize_fn = device.instrument(
                    f"stream_finalize[{method}]", self._finalize_fn)
            self._hot_jit = self._fold_fn
        else:
            # order-statistic rules fold per upload into the reservoir
            # only — a pre-summed wave has no per-client population
            self._fold_wave_fn = None
            # reservoir regime: the bounded stack IS the memory bound;
            # the finalize reuses the one-jit defended aggregate over the
            # static [K, ...] shape, so clip + rule + noise stay one
            # compile across rounds exactly like stack mode
            self._finalize_fn = make_defended_aggregate(
                method, trim_frac=trim_frac, byz_f=byz_f, krum_m=krum_m,
                gm_iters=gm_iters, gm_eps=gm_eps, norm_clip=norm_clip,
                noise_std=noise_std, seed=seed)
            if device is not None:
                # the reservoir finalize IS the sentry-monitored cache
                # (self._hot_jit): signatures land under the registered
                # stream_agg name so its verdicts carry the diff too
                self._finalize_fn = device.instrument(
                    f"stream_finalize[{method}]", self._finalize_fn,
                    sentry=sentry, sentry_name=f"stream_agg[{method}]")
            self._hot_jit = self._finalize_fn
        if sentry is not None:
            sentry.register(f"stream_agg[{method}]", self)

    # -- recompile-sentry probe (PerfRecorder.register_jit contract) ----------
    def _cache_size(self) -> int:
        n = int(self._hot_jit._cache_size())
        if self._fold_wave_fn is not None \
                and self._fold_wave_fn is not self._hot_jit:
            # the wave fold is part of the same monitored hot family: an
            # uncalled jit contributes 0, so per-upload-only rounds keep
            # the historical cache==1 pin and wave-only rounds read 1 too
            n += int(self._fold_wave_fn._cache_size())
            n += int(self._fold_sum_fn._cache_size())
        return n

    # -- crash consistency (utils/journal.py) --------------------------------
    @property
    def reference(self):
        """The round's clip reference (None between rounds) — the edge
        actors' resume path reads the restored round global here."""
        return self._reference

    def state_dict(self, include_reference: bool = False) -> dict:
        """Host snapshot of the MEAN fold state — the payload of the
        round journal's periodic durable snapshot.  Bit-exact contract:
        the accumulator leaves round-trip through numpy in their own
        ``acc_dtype``, ``wsum`` stays f32, so a restored fold continues
        the exact sequential reduction the uncrashed run would have.
        Reservoir (order-statistic) rules refuse: the Algorithm-R draw
        stream is not part of the durable contract — those rounds are
        abort-only (journal ``resumable=False``)."""
        if self.method != "mean":
            raise RuntimeError(
                f"state_dict: only the streaming MEAN fold snapshots; "
                f"{self.method!r} rounds are abort-only on crash")
        out = {
            "acc": (None if self._acc is None else
                    [np.asarray(l) for l in jax.tree.leaves(self._acc)]),
            "wsum": (np.float32(0.0) if self._wsum is None
                     else np.asarray(self._wsum, np.float32)[()]),
            "count": int(self.count),
            "weight_total": float(self.weight_total)}
        if include_reference:
            # edge actors snapshot the reference too: a respawned edge
            # has no live root sync to re-learn the round global from
            out["reference"] = [np.asarray(l)
                                for l in jax.tree.leaves(self._reference)]
        return out

    def load_state_dict(self, state: dict) -> None:
        """Restore a `state_dict` snapshot mid-round.  When the snapshot
        carries a ``reference`` the round is re-opened from it; otherwise
        the caller must have ``reset()`` the round first (the sync
        server restores the reference from its checkpointed global)."""
        if self.method != "mean":
            raise RuntimeError("load_state_dict: reservoir rounds are "
                               "abort-only; nothing to restore")
        if state.get("reference") is not None:
            self.reset(jax.tree.unflatten(
                self._treedef,
                [jnp.asarray(a) for a in state["reference"]]))
        if self._reference is None:
            raise RuntimeError("load_state_dict before reset(): the "
                               "round's clip reference is not set and "
                               "the snapshot carries none")
        if state.get("acc") is not None:
            self._acc = jax.tree.unflatten(
                jax.tree.structure(self._reference),
                [jnp.asarray(a) for a in state["acc"]])
            self._wsum = jnp.float32(state["wsum"])
        self.count = int(state["count"])
        self.weight_total = float(state["weight_total"])

    # -- round lifecycle -----------------------------------------------------
    def reset(self, reference) -> None:
        """Open a round against ``reference`` (the current global).  The
        reference is normalized to device arrays ONCE here — numpy
        round-0 globals and later jax outputs must key one jit entry,
        not two (the PR 5 double-compile class).  ``kind="delta"``
        replaces it with a cached zeros tree: async deltas clip against
        zero (clipping a delta against zero IS norm-clipping the delta)
        and pad with zero updates."""
        if self.kind == "delta":
            if self._reference is None:
                self._reference = jax.tree.map(
                    lambda r: jnp.zeros_like(jnp.asarray(r)), reference)
        else:
            self._reference = jax.tree.map(jnp.asarray, reference)
        self._acc = self._wsum = None
        self.count = 0
        self.weight_total = 0.0
        self._seen = 0
        if self._res_weights is not None:
            self._res_weights[:] = 0.0
        self._g_reservoir.set(0)

    def release(self) -> None:
        """Between runs: drop the round's reference and accumulator, so
        that nothing model-sized (on the device, or mirrored on the host
        inside those arrays) outlives the loop that used this object."""
        self._reference = self._acc = self._wsum = None

    def _pad_template(self):
        """What an unfolded reservoir slot holds: the reference — the
        current global for params kind (the zero diff every rule masks
        out), zeros for delta kind (reset already zeroed it)."""
        return jax.tree.map(np.asarray, self._reference)

    def _ensure_reservoir(self) -> None:
        if self._res_leaves is not None:
            return
        pad = self._pad_template()
        self._res_def = jax.tree.structure(pad)
        k = self.reservoir_k
        self._res_stack = jax.tree.map(
            lambda l: np.empty((k,) + np.shape(l), np.asarray(l).dtype), pad)
        self._res_leaves = jax.tree.leaves(self._res_stack)
        for buf, leaf in zip(self._res_leaves, jax.tree.leaves(pad)):
            buf[:] = np.asarray(leaf)
        self._res_weights = np.zeros(k, np.float32)

    def fold(self, upload, weight) -> None:
        """Fold one ADMITTED upload at arrival.  O(model) work, O(model)
        (mean) or O(K*model) (reservoir) standing memory — never a
        function of how many silos the round samples."""
        if self._reference is None:
            raise RuntimeError("fold() before reset(): the round's clip "
                               "reference is not set")
        if self.method != "mean":
            # validate BEFORE counting or drawing: a malformed upload
            # must fail loudly on every arrival, not only when it wins
            # an Algorithm-R slot (the mean fold's jit raises on its own
            # structure mismatch)
            self._ensure_reservoir()
            if jax.tree.structure(upload) != self._res_def:
                raise ValueError("upload does not match the aggregation "
                                 "template (treedef mismatch)")
        self._c_folds.inc()
        self.count += 1
        self.weight_total += float(weight)
        if self.method == "mean":
            if self._acc is None:
                self._acc = zeros_acc_like(self._reference)
                self._wsum = jnp.float32(0.0)
            self._acc, self._wsum = self._fold_fn(
                self._acc, self._wsum, upload, np.float32(weight),
                self._reference)
            return
        # reservoir regime (Algorithm R): the first K admitted uploads
        # fill slots; upload i > K replaces a uniform slot with
        # probability K/i — every admitted upload is in the reservoir
        # with equal probability K/n at round close
        self._seen += 1
        if self._seen <= self.reservoir_k:
            slot = self._seen - 1
        else:
            slot = int(self._res_rng.randint(self._seen))
            if slot >= self.reservoir_k:
                self._c_evict.inc()  # the arriving upload is the eviction
                return
            self._c_evict.inc()
        for buf, leaf in zip(self._res_leaves, jax.tree.leaves(upload)):
            buf[slot] = np.asarray(leaf)
        self._res_weights[slot] = np.float32(weight)
        self._g_reservoir.set(int((self._res_weights > 0).sum()))

    def fold_wave(self, stacked, weights) -> None:
        """Fold one compiled WAVE's stacked client updates at wave
        completion (the cross-device engine's seam): a device-side
        sequential scan over the ``[wave, ...]`` slot axis running the
        per-upload fold body per slot, so the fold order is the global
        cohort-slot order regardless of wave boundaries — wave-chunked,
        single-wave, and per-upload folds of the same updates land
        BIT-IDENTICAL accumulators.  Weight-0 padded slots contribute an
        exact ``+0.0`` (and do not count as folds); a wave of ALL pad
        slots folds as weight 0 instead of perturbing the normalizer.
        Standing memory stays O(model) — the wave stack is the caller's
        static device buffer, never banked here."""
        if self._reference is None:
            raise RuntimeError("fold_wave() before reset(): the round's "
                               "clip reference is not set")
        if self.method != "mean":
            raise RuntimeError(
                f"fold_wave: only the streaming MEAN folds pre-stacked "
                f"waves; order-statistic rules ({self.method!r}) need the "
                f"per-client population — fold() each upload into the "
                f"reservoir instead")
        w_host = np.asarray(weights, np.float32)
        live = int((w_host > 0).sum())
        if self._acc is None:
            self._acc = zeros_acc_like(self._reference)
            self._wsum = jnp.float32(0.0)
        self._acc, self._wsum = self._fold_wave_fn(
            self._acc, self._wsum, stacked,
            jnp.asarray(weights, jnp.float32), self._reference)
        self._c_folds.inc(live)
        self.count += live
        # slot-order sequential host adds — the per-upload path's exact
        # weight_total arithmetic (np.sum's pairwise order would differ)
        for w in w_host:
            self.weight_total += float(w)

    def fold_sum(self, wave_sum, weights, wave_weight) -> None:
        """Fold one compiled wave that comes as its SUM: ``wave_sum`` is
        ``sum_i w_i * update_i`` over the wave's slots in slot order, in
        the accumulator's dtype (`parallel/cohort.train_cohort_sum`), and
        ``wave_weight`` the running sum of the weights made beside it
        (a device scalar).  The round's first such wave IS the
        accumulator (nothing is added, nothing copied), so a one-wave
        round closes on the very bits `fold_wave` gives the stacked
        results; a later wave is added leaf by leaf, which orders a
        many-wave round's additions by wave and agrees with the
        slot-order fold to rounding.  Only a fold that reads no single
        upload can take a sum: ``norm_clip`` clips each one and is
        refused here.  ``weights`` are the slots' host weights, for the
        counts `fold_wave` keeps."""
        if self._reference is None:
            raise RuntimeError("fold_sum() before reset(): the round's "
                               "reference is not set")
        if self.method != "mean" or self.norm_clip > 0:
            raise RuntimeError(
                "fold_sum: a pre-summed wave suits the plain streaming "
                "mean only; a clip or an order-statistic rule reads each "
                "upload (fold_wave / fold)")
        w_host = np.asarray(weights, np.float32)
        live = int((w_host > 0).sum())
        if self._acc is None:
            self._acc = wave_sum
            self._wsum = jnp.asarray(wave_weight, jnp.float32)
        else:
            self._acc, self._wsum = self._fold_sum_fn(
                self._acc, self._wsum, wave_sum,
                jnp.asarray(wave_weight, jnp.float32))
        self._c_folds.inc(live)
        self.count += live
        for w in w_host:
            self.weight_total += float(w)

    def finalize(self, step):
        """Close the round: the streamed mean (or the reservoir's robust
        rule) against the reset-time reference, noise folded by ``step``.
        Callers must guard the zero-fold round (skip aggregation) —
        same contract as `make_defended_aggregate` weights."""
        if self.count == 0:
            raise RuntimeError("finalize() with no folded uploads; the "
                               "caller must skip aggregation on an empty "
                               "round")
        # one interval: this module's histogram and, under a caller's
        # open span site, that round's span and ledger phase as well
        with trace.child("finalize.dispatch", phase="fold",
                         hist=self._h_finalize):
            if self.method == "mean":
                out = self._finalize_fn(self._acc, self._wsum,
                                        self._reference, step)
                # drop our handle to the finalized accumulator so a stale
                # buffer is never folded into the next round
                self._acc = self._wsum = None
            else:
                out = self._finalize_fn(self._reference, self._res_stack,
                                        self._res_weights.copy(), step)
        return out
