"""Static device-sized waves: the unit of compiled cross-device training.

A mega-cohort round (1k-100k sampled clients) cannot train as one vmap —
the stacked cohort would not fit HBM, and a dynamic cohort shape would
re-jit every round.  `plan_waves` chops the sampled cohort into
fixed-size waves (the last one padded with weight-0 slots, the
`gather_cohort` convention), so every wave of every round hits ONE jit
cache entry; `make_wave_fn` compiles the wave: local training over the
stacked client axis (`parallel/cohort.train_cohort` — vmap, or clients in
sequence for a conv model or a tree too large to hold once a client, on
one chip; shard_map over the mesh's ``clients`` axis), plus the wave
SUMMARY the host needs for admission/health — the weighted partial mean,
the weight total, any per-client aux reductions, and the admission
screen's statistics (`admission_stats`: is the mean finite, how far is
each leaf from the round's global) — computed on device so the host
never walks the ``[wave, ...]`` stack, nor the mean.  `make_summed_wave_fn`
is the wave of an engine that reads nothing of one client's result: the
clients train in sequence and only their weighted sum leaves the program.

Per-client rng = fold_in(round_rng, global cohort slot) via the wave's
``offset`` (a traced scalar, so chunking does not retrace): a
wave-chunked round trains bit-identically to a single-wave round, and
to the plain FedAvg cohort engine on the same seed.

`WaveAdmission` is the per-wave screen: structural fingerprint, finite
guard, and a rolling median+MAD norm-outlier screen over the wave
summary (the same statistics `robust/admission.py` runs per upload on
the live wire — reused here at wave granularity, because inside a
compiled wave there is no per-client payload to screen).  A rejected
wave contributes weight 0: its clients' work is discarded for the
round, which is the honest granularity of a compiled wave.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from fedml_tpu.core.pytree import acc_dtype
# per-wave screens reuse the live admission pipeline's statistics
# helpers so wave screening can never drift from upload screening
from fedml_tpu.robust.admission import (AdmissionVerdict, _all_finite,
                                        _leaves, _update_norm,
                                        norm_outlier_threshold,
                                        params_fingerprint)
from fedml_tpu.obs import telemetry

Pytree = Any


@dataclasses.dataclass(frozen=True)
class Wave:
    """One static-size slice of the round's sampled cohort.

    ``ids``: the LIVE client ids (length <= wave_size; `gather_cohort`
    pads the rest with weight-0 dummy slots).  ``offset``: this wave's
    first global cohort-slot index — the per-client rng fold anchor.
    """
    ids: np.ndarray
    offset: int

    @property
    def n_live(self) -> int:
        return len(self.ids)


def plan_waves(ids: Sequence[int], wave_size: int) -> List[Wave]:
    """Chop the sampled cohort into ``wave_size`` chunks (last padded by
    the gather).  Every wave is the SAME static shape, so the whole
    round — any cohort size — costs one jit cache entry."""
    if wave_size < 1:
        raise ValueError(f"wave_size must be >= 1, got {wave_size}")
    ids = np.asarray(ids, dtype=np.int64)
    return [Wave(ids=ids[lo:lo + wave_size], offset=lo)
            for lo in range(0, max(len(ids), 1), wave_size)]


def _wave_summary(stacked: Pytree, w: jax.Array, aux: Dict[str, jax.Array],
                  psum_axis: Optional[str] = None):
    """Device-side wave summary: weighted partial mean (acc-dtype
    accumulation, the `tree_weighted_mean` contract), weight total, and
    weighted sums of per-client aux arrays.  With ``psum_axis`` the
    reductions ride ICI (the shard_map path)."""
    def allsum(x):
        return jax.lax.psum(x, psum_axis) if psum_axis is not None else x

    total = allsum(jnp.sum(w))
    # all-pad waves (total 0) divide by the guard, not 0 — the engine
    # skips them by weight before the mean is ever read
    ratio = w / jnp.maximum(total, 1e-6)

    def _mean(x):
        acc = acc_dtype(x.dtype)
        r = ratio.reshape((-1,) + (1,) * (x.ndim - 1))
        return allsum(jnp.sum(x.astype(acc) * r.astype(acc),
                              axis=0)).astype(x.dtype)

    mean = jax.tree.map(_mean, stacked)
    return mean, total, {k: allsum(v) for k, v in _aux_sums(aux, w).items()}


def _aux_sums(aux: Dict[str, jax.Array], w: jax.Array):
    """Per-client aux arrays -> their sums weighted by the clients' ``w``."""
    return {k: jnp.sum(v.astype(jnp.float32)
                       * w.reshape((-1,) + (1,) * (v.ndim - 1)), axis=0)
            for k, v in aux.items()}


def mean_of_sum(wave_sum: Pytree, total: jax.Array, like: Pytree) -> Pytree:
    """A summed wave's mean, in ``like``'s dtypes (an all-pad wave's
    total 0 divides by `_wave_summary`'s guard)."""
    guard = jnp.maximum(total, 1e-6)
    return jax.tree.map(
        lambda a, p: (a / guard.astype(a.dtype)).astype(p.dtype),
        wave_sum, like)


def admission_stats(mean: Pytree, reference: Pytree) -> Dict[str, jax.Array]:
    """What `WaveAdmission.screen` needs of a wave's mean, computed where
    the mean lives: ``finite`` (every floating leaf finite) and
    ``leaf_sumsq``, each leaf's ``sum((mean - reference)^2)`` in float32
    (the host adds them in float64 and takes the root)."""
    leaves = list(zip(jax.tree.leaves(mean), jax.tree.leaves(reference)))
    finite = jnp.stack([jnp.all(jnp.isfinite(m)) for m, _ in leaves
                        if jnp.issubdtype(m.dtype, jnp.floating)]
                       or [jnp.bool_(True)])
    return {"finite": jnp.all(finite),
            "leaf_sumsq": jnp.stack([
                jnp.sum(jnp.square(m.astype(jnp.float32)
                                   - r.astype(jnp.float32)))
                for m, r in leaves])}


def make_summed_wave_fn(train_summed: Callable):
    """Compile one wave of which only the sum is read:
    ``wave_fn(params, wave_data, rng, offset) -> (wave_sum, weights,
    wave_weight, aux_sums, stats)``.

    ``train_summed(params, wave_data, rng, offset) -> (sum, weight total,
    aux)`` trains the wave's clients one after another and carries
    ``sum_i w_i * result_i`` (`parallel/cohort.train_cohort_sum`); ``aux``
    maps names to per-client arrays, reduced here to weighted sums as
    `make_wave_fn` reduces them.  ``wave_sum`` is what
    `StreamingAggregator.fold_sum` takes: no tree a client, and no mean,
    is made.  ``stats`` are `admission_stats` of the wave's mean (divided
    out leaf by leaf inside the reductions, never kept)."""
    @jax.jit
    def wave_fn(params, wave_data, rng, offset):
        wave_sum, total, aux = train_summed(params, wave_data, rng, offset)
        w = wave_data["num_samples"].astype(jnp.float32)
        return (wave_sum, w, total, _aux_sums(aux, w), admission_stats(
            mean_of_sum(wave_sum, total, params), params))
    return wave_fn


def make_wave_fn(make_stacked: Callable, mesh: Optional[Mesh] = None):
    """Compile one wave: ``wave_fn(params, wave_data, rng, offset) ->
    (stacked_uploads, weights, wave_mean, wave_weight, aux_sums)``.

    ``make_stacked(params, wave_data, rng, offset) -> (stacked, aux)``
    is the jit-able per-wave trainer (typically `train_cohort` over a
    local trainer); ``aux`` maps names to per-client ``[wave, ...]``
    arrays that reduce to weighted sums (e.g. FedNova's tau terms).

    ``offset`` must be a traced scalar (pass ``jnp.int32(lo)``) so every
    wave of every round shares ONE jit cache entry.  On a mesh the wave
    shards over the ``clients`` axis (stacked outputs stay sharded, the
    summary is psum'd replicated); the stacked outputs are identical to
    the single-chip wave bit for bit (the `train_cohort` rng contract),
    so the host-ordered streaming fold downstream agrees too."""
    if mesh is None:
        @jax.jit
        def wave_fn(params, wave_data, rng, offset):
            stacked, aux = make_stacked(params, wave_data, rng, offset)
            w = wave_data["num_samples"].astype(jnp.float32)
            mean, total, aux_sums = _wave_summary(stacked, w, aux)
            return stacked, w, mean, total, aux_sums
        return wave_fn

    def _sharded(params, wave_data, rng, offset):
        # per-device: wave_data leaves are the local shard [W/D, ...];
        # params/rng arrive replicated — mark them device-varying so the
        # local-train scan carry typechecks (parallel/cohort.py idiom)
        params = jax.lax.pcast(params, ("clients",), to="varying")
        rng = jax.lax.pcast(rng, ("clients",), to="varying")
        local_c = wave_data["num_samples"].shape[0]
        local_off = offset + jax.lax.axis_index("clients") * local_c
        stacked, aux = make_stacked(params, wave_data, rng, local_off)
        w = wave_data["num_samples"].astype(jnp.float32)
        mean, total, aux_sums = _wave_summary(stacked, w, aux,
                                              psum_axis="clients")
        return stacked, w, mean, total, aux_sums

    sharded = jax.shard_map(
        _sharded, mesh=mesh,
        in_specs=(P(), P("clients"), P(), P()),
        out_specs=(P("clients"), P("clients"), P(), P(), P()))
    n_dev = mesh.shape["clients"]

    @jax.jit
    def wave_fn(params, wave_data, rng, offset):
        W = wave_data["num_samples"].shape[0]
        if W % n_dev:  # static shape — checked at trace time
            raise ValueError(
                f"wave size {W} not divisible by the mesh clients axis "
                f"({n_dev}); pick --wave_size as a multiple of the "
                f"device count")
        return sharded(params, wave_data, rng, offset)

    return wave_fn


def make_scaffold_wave_fn(scaffold_local, lr: float):
    """SCAFFOLD's wave (single-chip vmap; the control variates are
    host-resident stacked state, `algorithms/fedavg.py` convention):

    ``wave_fn(params, wave_data, rng, offset, c_global, c_cohort) ->
    (stacked_y, weights, wave_mean, wave_weight, new_c_cohort,
    c_delta_sum, live_count)``

    Padded slots (weight 0) freeze their aliased ``c`` rows and
    contribute nothing to the c-delta sum, exactly like the in-tree
    `algorithms/scaffold.Scaffold._core`."""

    @jax.jit
    def wave_fn(params, wave_data, rng, offset, c_global, c_cohort):
        n = wave_data["num_samples"].shape[0]
        # the train_cohort rng convention (fold_in(rng, global slot)),
        # restated because scaffold_local's extra per-client c_diff arg
        # doesn't fit train_cohort's (params, batch, rng) vmap — the
        # same restatement algorithms/scaffold.Scaffold._core makes,
        # and the engine's scaffold-vs-Scaffold parity test pins all
        # three spellings together (a drifting convention fails there)
        rngs = jax.vmap(lambda i: jax.random.fold_in(rng, i))(
            jnp.arange(n) + offset)
        batches = {k: v for k, v in wave_data.items() if k != "num_samples"}
        c_diffs = jax.tree.map(lambda cg, ci: cg[None] - ci,
                               c_global, c_cohort)
        ys, ks = jax.vmap(scaffold_local, in_axes=(None, 0, 0, 0))(
            params, batches, rngs, c_diffs)
        w = wave_data["num_samples"].astype(jnp.float32)
        live = (w > 0).astype(jnp.float32)
        k_safe = jnp.maximum(ks, 1.0)
        # c_i+ = c_i − c + (x − y_i)/(K·lr); frozen for padded slots
        new_c = jax.tree.map(
            lambda ci, cg, x, y: jnp.where(
                live.reshape((-1,) + (1,) * x.ndim) > 0,
                ci - cg[None] + (x[None] - y)
                / (k_safe.reshape((-1,) + (1,) * x.ndim) * lr),
                ci),
            c_cohort, c_global, params, ys)
        c_delta = jax.tree.map(
            lambda nci, ci: jnp.sum(
                (nci - ci) * live.reshape((-1,) + (1,) * (nci.ndim - 1)),
                axis=0),
            new_c, c_cohort)
        mean, total, _ = _wave_summary(ys, w, {})
        return ys, w, mean, total, new_c, c_delta, jnp.sum(live)

    return wave_fn


class WaveAdmission:
    """Per-wave admission: the structural fingerprint, finite guard, and
    rolling median+MAD norm screen of `robust.AdmissionPipeline`, run
    against each wave's weighted partial mean instead of per upload.

    Rejection reasons land in
    ``fedml_cohort_wave_rejected_total{reason}`` and in the in-process
    ``rejected`` mirror; there is no trust ledger — a wave index is a
    position in a freshly-sampled cohort, not a persistent identity, so
    striking it would quarantine an arbitrary slice of future cohorts.

    The norm history resets at ``round_start`` (unlike the live
    pipeline's cross-round silo history): wave means of ONE round are
    the exchangeable population — update norms drift round-over-round
    as training converges (and change regime outright when, e.g.,
    SCAFFOLD's control variates arm after round 0), so a cross-round
    history rejects honest waves on drift alone (observed, pinned).
    Consequence: the screen arms only in rounds with more than
    ``norm_min_history`` live waves — i.e. at the mega-cohort scale it
    exists for (100k clients / 256-wide waves = ~390 screened waves),
    while a 4-wave smoke run keeps structure/finite screening only.
    """

    REASONS = ("fingerprint", "nonfinite", "norm_outlier")

    def __init__(self, template, *, norm_k: float = 6.0,
                 norm_window: int = 64, norm_min_history: int = 8,
                 norm_screen: bool = True):
        if norm_window < 1 or norm_min_history < 1:
            raise ValueError("norm_window and norm_min_history must be >= 1")
        import collections
        self.fingerprint = params_fingerprint(template)
        self.norm_k = norm_k
        self.norm_min_history = norm_min_history
        self.norm_screen = norm_screen
        self._norms = collections.deque(maxlen=norm_window)
        reg = telemetry.get_registry()
        self._c_rejected = {r: reg.counter(
            "fedml_cohort_wave_rejected_total", reason=r)
            for r in self.REASONS}
        self.rejected: Dict[str, int] = {r: 0 for r in self.REASONS}
        self.admitted = 0
        # identity-keyed f64 host mirror of the round reference: one
        # conversion per round, not one per wave (AdmissionPipeline idiom)
        self._ref_cache: Tuple[object, Optional[list]] = (None, None)

    def round_start(self) -> None:
        """Open a round: clear the norm history (see class docstring —
        the wave population is per-round, a cross-round history rejects
        honest waves on convergence drift)."""
        self._norms.clear()

    def _reject(self, reason: str,
                norm: Optional[float] = None) -> AdmissionVerdict:
        self.rejected[reason] += 1
        self._c_rejected[reason].inc()
        return AdmissionVerdict(False, reason=reason, norm=norm)

    def norm_threshold(self) -> Optional[float]:
        return norm_outlier_threshold(self._norms, self.norm_k,
                                      self.norm_min_history)

    def screen(self, wave_mean, global_params=None,
               stats=None) -> AdmissionVerdict:
        """Screen one wave's summary against the round's global.  Order
        matters: structure before any tree math (the pipeline's rule).

        With ``stats`` (`admission_stats`, read from the device) the mean
        is not walked: ``wave_mean`` then only has to show its structure
        (device arrays or shapes do), the finite flag is the device's and
        the norm is the root of the leaves' squared distances, added in
        float64.  Without, ``wave_mean`` is a host tree and the walk is
        the live pipeline's own."""
        try:
            fp_ok = params_fingerprint(wave_mean) == self.fingerprint
        except Exception:  # noqa: BLE001 — unhashable garbage summary
            fp_ok = False
        if not fp_ok:
            return self._reject("fingerprint")
        if stats is not None:
            if not bool(stats["finite"]):
                return self._reject("nonfinite")
            norm = float(np.sqrt(np.sum(
                np.asarray(stats["leaf_sumsq"], np.float64))))
            return self._screen_norm(norm)
        if not _all_finite(wave_mean):
            return self._reject("nonfinite")
        if self._ref_cache[0] is not global_params:
            # _leaves (not jax.tree.leaves): the canonical flatten order
            # _update_norm zips against
            self._ref_cache = (global_params,
                               [np.asarray(leaf, np.float64)
                                for leaf in _leaves(global_params)])
        return self._screen_norm(
            _update_norm(wave_mean, self._ref_cache[1]))

    def _screen_norm(self, norm: float) -> AdmissionVerdict:
        if self.norm_screen:
            thresh = self.norm_threshold()
            if thresh is not None and norm > thresh:
                return self._reject("norm_outlier", norm)
            self._norms.append(norm)
        self.admitted += 1
        return AdmissionVerdict(True, norm=norm)
