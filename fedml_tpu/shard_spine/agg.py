"""The sharded streaming fold: `core.stream_agg.StreamingAggregator`'s
state, laid out per `ShardPlan` shard — each device folds its shard of
every arriving upload, and nothing O(model) ever lives on one device.

Duck-type contract: this class speaks the exact `StreamingAggregator`
protocol the live server, the round journal, and the perf observatory
already consume — ``reset`` / ``fold`` / ``fold_wave`` / ``finalize`` /
``state_dict`` / ``load_state_dict`` / ``_cache_size`` / ``count`` /
``weight_total`` / ``reference`` / ``defended`` / ``method`` — so the
round lifecycle in `algorithms/cross_silo.py` is unchanged; only the
wire path (per-shard slices) is new.

Fold math (the parity contract tests/test_shard_spine.py pins):

* **unclipped** — per shard, ``acc_s += u_s * w`` elementwise: the same
  sequential per-element reduction the replicated fold runs, so sharded
  and replicated accumulators agree BIT FOR BIT at any S.
* **clipped** — the clip scale needs the GLOBAL update norm, so it is
  two-phase (arXiv 2004.13336's sharded weight-update discipline): each
  shard computes its slice's partial ``sum((u-g)^2)``, one tiny jit
  combines them into ``min(1, clip/||u-g||)``, and every shard folds
  ``g + (u-g)*scale`` with that scalar.  At S=1 the partial IS the full
  norm computed in the replicated path's exact op order — bit-identical;
  at S>1 the partials sum in shard order instead of leaf order, so the
  scale (and everything after it) agrees to float tolerance, not bits.
* **noise** — sigma>0 draws per shard (`fold_in(key, shard)` past the
  round fold): S=1 reproduces the replicated stream bit-for-bit; S>1
  streams are documented-different (same N(0, sigma) distribution).

Finalize backends: ``fused=False`` is the XLA compose (division + noise
per shard); ``fused=True`` wires `core.pallas_agg.make_fused_shard_finalize`
— clip(at fold) + weighted mean + weak-DP noise complete as ONE Pallas
kernel launch per shard (compiled on a TPU, interpreted on the CPU:
`core.pallas_agg.pallas_interpret`).  sigma=0 fused is
bit-identical to the XLA compose for f32 models (same elementwise f32
division); the kernels register with the device observatory so the
compile ledger names them and the MFU gauge finally measures an
accelerator-bound hot loop.

Memory: per shard, O(model/S) accumulator + O(model/S) reference; with
a mesh (``model`` axis), each shard's state is committed to its own
device, so per-DEVICE memory scales ~1/S.
"""

from __future__ import annotations

import logging
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.core.stream_agg import zeros_acc_like
from fedml_tpu.obs import telemetry
from fedml_tpu.shard_spine.plan import (ShardPlan, _leaf_key,
                                         _shard_key)

log = logging.getLogger(__name__)


class ShardedStreamingAggregator:
    """O(model/S)-per-shard fold-at-arrival defended-mean aggregation.

    ``plan``: the deterministic layout (`plan.ShardPlan`).  ``mesh``: an
    optional mesh with a ``model`` axis of size S — each shard's fold
    state is then committed to its own device; None keeps everything on
    the default device (same math, the honest 1-chip posture).

    Mean only: order-statistic rules need the per-upload population,
    which a sharded fold deliberately never materializes — they refuse
    loudly here (use ``--agg_mode stream --stream_reservoir`` on the
    replicated path instead).
    """

    def __init__(self, plan: ShardPlan, template, *, kind: str = "params",
                 norm_clip: float = 0.0, noise_std: float = 0.0,
                 seed: int = 0, fused: bool = False,
                 interpret: Optional[bool] = None, mesh=None,
                 sentry=None, device=None):
        if kind != "params":
            raise ValueError(
                f"the sharded spine folds cross-silo params uploads only "
                f"(kind='params'); got kind={kind!r} — the async delta "
                f"path is not sharded")
        if norm_clip < 0 or noise_std < 0:
            raise ValueError(f"norm_clip/noise_std must be >= 0, got "
                             f"{norm_clip}/{noise_std}")
        self.plan = plan
        self.method = "mean"
        self.kind = kind
        self.norm_clip = float(norm_clip)
        self.noise_std = float(noise_std)
        self.seed = int(seed)
        self.fused = bool(fused)
        if fused and interpret is None:
            from fedml_tpu.core.pallas_agg import pallas_interpret
            interpret = pallas_interpret("shard_finalize")
        self.interpret = bool(interpret)
        self.defended = norm_clip > 0 or noise_std > 0
        self._treedef = jax.tree.structure(template)
        self._devices = plan.shard_devices(mesh) if mesh is not None \
            else None

        S = plan.num_shards
        self._weight_flags = [plan.slice_weight_flags(s) for s in range(S)]
        # per-shard hot jits — each a fresh jax.jit, so the cache-size
        # pin (exactly one entry per shard per family after round 0) and
        # the recompile sentry see THIS aggregator's compiles only
        self._fold_fns = [self._make_fold(s) for s in range(S)]
        self._wave_fns = [self._make_fold_wave(s) for s in range(S)]
        self._sumsq_fns = ([self._make_sumsq(s) for s in range(S)]
                           if norm_clip > 0 else None)
        self._sumsq_wave_fns = ([self._make_sumsq_wave(s)
                                 for s in range(S)]
                                if norm_clip > 0 else None)
        self._scale_fn = jax.jit(self._combine_scale) if norm_clip > 0 \
            else None
        self._wadd_fn = jax.jit(lambda ws, w: ws + w, donate_argnums=(0,))
        self._wadd_wave_fn = jax.jit(
            lambda ws, w: jax.lax.scan(
                lambda c, wi: (c + wi, None), ws, w)[0],
            donate_argnums=(0,))
        if fused:
            from fedml_tpu.core.pallas_agg import make_fused_shard_finalize
            self._finalize_fns = [
                make_fused_shard_finalize(
                    noise_std=noise_std, seed=seed, shard_salt=s,
                    interpret=self.interpret)
                for s in range(S)]
        else:
            self._finalize_fns = [self._make_finalize(s) for s in range(S)]
        # the raw jits, kept for the cache probe (device instrumentation
        # wraps the CALLED handles below but forwards _cache_size)
        self._hot_jits = (self._fold_fns + self._wave_fns
                          + self._finalize_fns + [self._wadd_fn,
                                                  self._wadd_wave_fn]
                          + (self._sumsq_fns or [])
                          + (self._sumsq_wave_fns or [])
                          + ([self._scale_fn] if self._scale_fn else []))
        if device is not None:
            fam = "shard_spine[mean]"
            self._fold_fns = [
                device.instrument(f"shard_fold[s{s}]", fn, sentry=sentry,
                                  sentry_name=fam)
                for s, fn in enumerate(self._fold_fns)]
            fin_label = "fused_finalize" if fused else "shard_finalize"
            self._finalize_fns = [
                device.instrument(f"{fin_label}[s{s}]", fn, sentry=sentry,
                                  sentry_name=fam)
                for s, fn in enumerate(self._finalize_fns)]
        if sentry is not None:
            sentry.register("shard_spine[mean]", self)

        reg = telemetry.get_registry()
        self._c_folds = reg.counter("fedml_stream_folds_total")
        self._c_slices = reg.counter("fedml_shard_slices_total")
        self._c_fused = reg.counter("fedml_shard_fused_launches_total")
        self._g_acc_bytes = reg.gauge("fedml_shard_acc_bytes")
        self._h_finalize = reg.histogram("fedml_shard_finalize_seconds")

        # per-round state: one slice dict per shard
        self._reference: Optional[List[dict]] = None
        self._acc: Optional[List[dict]] = None
        self._wsum = None
        self.count = 0
        self.weight_total = 0.0

    # -- jit factories -------------------------------------------------------
    def _make_fold(self, shard: int):
        flags = self._weight_flags[shard]
        clip = self.norm_clip

        def _fold(acc, upload, weight, reference, scale):
            out = {}
            for k, flag in zip(sorted(acc), flags):
                a, u, g = acc[k], upload[k], reference[k]
                if clip > 0 and flag:
                    # clip_update's exact per-leaf apply, with the
                    # (two-phase) global scale passed in as a scalar
                    u = g + (u - g) * scale.astype(u.dtype)
                out[k] = a + u.astype(a.dtype) * weight.astype(a.dtype)
            return out

        return jax.jit(_fold, donate_argnums=(0,))

    def _make_fold_wave(self, shard: int):
        flags = self._weight_flags[shard]
        clip = self.norm_clip

        def _fold_wave(acc, stacked, weights, reference, scales):
            def body(carry, xs):
                upload, w, s = xs
                out = {}
                for k, flag in zip(sorted(carry), flags):
                    a, u, g = carry[k], upload[k], reference[k]
                    if clip > 0 and flag:
                        u = g + (u - g) * s.astype(u.dtype)
                    out[k] = a + u.astype(a.dtype) * w.astype(a.dtype)
                return out, None

            acc, _ = jax.lax.scan(body, acc, (stacked, weights, scales))
            return acc

        return jax.jit(_fold_wave, donate_argnums=(0,))

    @staticmethod
    def _slice_sumsq(upload, reference, flags):
        """_masked_global_norm's exact op order over one shard's
        pieces: diff in the leaf's own dtype, squared in f32, summed
        sequentially in slice-key order.  ONE definition — the
        per-upload and wave clip norms must never desynchronize."""
        total = 0.0
        for k, flag in zip(sorted(upload), flags):
            if flag:
                d = upload[k] - reference[k]
                total = total + jnp.sum(jnp.square(d.astype(jnp.float32)))
        return jnp.asarray(total, jnp.float32)

    def _make_sumsq(self, shard: int):
        flags = self._weight_flags[shard]
        return jax.jit(lambda upload, reference: self._slice_sumsq(
            upload, reference, flags))

    def _make_sumsq_wave(self, shard: int):
        flags = self._weight_flags[shard]

        def _sumsq_wave(stacked, reference):
            return jax.vmap(lambda u: self._slice_sumsq(
                u, reference, flags))(stacked)

        return jax.jit(_sumsq_wave)

    def _combine_scale(self, partials):
        # clip_update's scale formula over the summed shard partials
        total = 0.0
        for p in partials:
            total = total + p
        norm = jnp.sqrt(total)
        return jnp.minimum(1.0, self.norm_clip
                           / jnp.maximum(norm, 1e-12))

    def _make_finalize(self, shard: int):
        noise = self.noise_std
        seed = self.seed
        S = self.plan.num_shards

        def _finalize(acc, wsum, reference, step):
            out = {k: (acc[k] / wsum.astype(acc[k].dtype)).astype(
                jnp.asarray(reference[k]).dtype) for k in sorted(acc)}
            if noise > 0:
                from fedml_tpu.core.robust import add_gaussian_noise
                key = jax.random.fold_in(jax.random.key(seed),
                                         jnp.asarray(step, jnp.uint32))
                if S > 1:
                    # decorrelate the per-shard streams; at S=1 the key
                    # chain (and the per-leaf split in
                    # add_gaussian_noise) reproduces the replicated
                    # path's draw bit for bit
                    key = jax.random.fold_in(key, jnp.uint32(shard))
                out = add_gaussian_noise(out, key, noise)
            return out

        return jax.jit(_finalize)

    # -- recompile-sentry probe ----------------------------------------------
    def _cache_size(self) -> int:
        total = 0
        for fn in self._hot_jits:
            total += int(fn._cache_size())
        return total

    # -- round lifecycle -----------------------------------------------------
    @property
    def reference(self):
        return self._reference

    def shard_device(self, shard: int):
        """The device shard ``shard``'s fold state is committed to, or
        None when the host has fewer devices than shards and everything
        shares the default device."""
        return None if self._devices is None else self._devices[shard]

    def _place(self, shard: int, slice_body: dict) -> dict:
        """Commit one shard's pieces to its device (consistent committed
        placement = one jit cache entry per shard; the PR 13 lesson)."""
        if self._devices is None:
            return {k: jnp.asarray(v) for k, v in slice_body.items()}
        dev = self._devices[shard]
        return {k: jax.device_put(v, dev) for k, v in slice_body.items()}

    def _split_body(self, tree_or_leaves) -> List[dict]:
        """Full tree (or ordered leaf list) -> per-shard slice BODIES
        (the inner ``{leaf_key: piece}`` dicts)."""
        leaves = (tree_or_leaves if isinstance(tree_or_leaves, list)
                  else [np.asarray(x)
                        for x in jax.tree.leaves(tree_or_leaves)])
        slices = self.plan.split_leaves(leaves)
        return [sl[_shard_key(s)] for s, sl in enumerate(slices)]

    def reset(self, reference) -> None:
        host = jax.tree.map(np.asarray, reference)
        self._reference = [self._place(s, body) for s, body in
                           enumerate(self._split_body(host))]
        self._acc = None
        self._wsum = None
        self.count = 0
        self.weight_total = 0.0

    def _ensure_acc(self) -> None:
        if self._acc is not None:
            return
        self._acc = [self._place(s, zeros_acc_like(ref))
                     for s, ref in enumerate(self._reference)]
        self._wsum = jnp.float32(0.0)
        self._g_acc_bytes.set(max(
            sum(int(np.prod(v.shape or (1,))
                    * jnp.dtype(v.dtype).itemsize)
                for v in body.values())
            for body in self._acc))

    def _slice_bodies(self, slices: Sequence[dict]) -> List[dict]:
        """Validate + unwrap wire slices (``{"s<idx>": body}``) into
        per-shard bodies; plain bodies pass through."""
        S = self.plan.num_shards
        if len(slices) != S:
            raise ValueError(f"fold_slices needs {S} slices, got "
                             f"{len(slices)}")
        out = []
        for s, sl in enumerate(slices):
            body = sl.get(_shard_key(s)) if isinstance(sl, dict) \
                and _shard_key(s) in sl else sl
            out.append(body)
        return out

    def fold_slices(self, slices: Sequence[dict], weight) -> None:
        """Fold one ADMITTED upload, delivered as its S shard slices, at
        arrival.  Per shard: O(model/S) work on that shard's device."""
        if self._reference is None:
            raise RuntimeError("fold_slices() before reset(): the "
                               "round's clip reference is not set")
        bodies = [self._place(s, b) for s, b in
                  enumerate(self._slice_bodies(slices))]
        self._ensure_acc()
        w = np.float32(weight)
        scale = np.float32(1.0)
        if self.norm_clip > 0:
            # partials come back committed to their shards' devices;
            # combine from HOST scalars so the tiny scale jit never
            # sees mixed placements, and hand each shard's fold the
            # scale as an uncommitted host scalar for the same reason
            partials = tuple(
                np.asarray(self._sumsq_fns[s](bodies[s],
                                              self._reference[s]))
                for s in range(self.plan.num_shards))
            scale = np.asarray(self._scale_fn(partials))
        for s in range(self.plan.num_shards):
            self._acc[s] = self._fold_fns[s](
                self._acc[s], bodies[s], w, self._reference[s], scale)
        self._wsum = self._wadd_fn(self._wsum, jnp.float32(w))
        self._c_folds.inc()
        self._c_slices.inc(self.plan.num_shards)
        self.count += 1
        self.weight_total += float(weight)

    def fold(self, upload, weight) -> None:
        """`StreamingAggregator.fold` twin: a full-tree upload is split
        host-side and folded per shard (tests, and any caller that never
        saw per-shard wire slices)."""
        self.fold_slices(
            [{_shard_key(s): b} for s, b in
             enumerate(self._split_body(upload))], weight)

    def fold_wave(self, stacked, weights) -> None:
        """Fold one compiled wave's ``[wave, ...]`` stacked updates: the
        wave stack is split per shard (slot axis intact) and each shard
        runs the sequential per-slot scan — the replicated
        `fold_wave`'s exact fold order, so wave-chunked == per-upload
        folds per shard.  Weight-0 padded slots contribute an exact
        ``+0.0``."""
        if self._reference is None:
            raise RuntimeError("fold_wave() before reset(): the round's "
                               "clip reference is not set")
        w_host = np.asarray(weights, np.float32)
        wave = int(w_host.shape[0])
        leaves = [np.asarray(x) for x in jax.tree.leaves(stacked)]
        bodies = [self._place(s, b) for s, b in enumerate(
            self._split_body_stacked(leaves, wave))]
        self._ensure_acc()
        w_dev = w_host  # uncommitted host arrays follow each shard's
        #                 committed placement inside the per-shard jits
        if self.norm_clip > 0:
            partials = tuple(
                np.asarray(self._sumsq_wave_fns[s](bodies[s],
                                                   self._reference[s]))
                for s in range(self.plan.num_shards))
            scales = np.asarray(self._scale_fn(partials))
        else:
            scales = np.ones((wave,), np.float32)
        for s in range(self.plan.num_shards):
            self._acc[s] = self._wave_fns[s](
                self._acc[s], bodies[s], w_dev, self._reference[s],
                scales)
        self._wsum = self._wadd_wave_fn(self._wsum, w_dev)
        live = int((w_host > 0).sum())
        self._c_folds.inc(live)
        self._c_slices.inc(live * self.plan.num_shards)
        self.count += live
        for w in w_host:   # the per-upload path's exact host arithmetic
            self.weight_total += float(w)

    def _split_body_stacked(self, leaves: List[np.ndarray],
                            wave: int) -> List[dict]:
        """Split ``[wave, ...]``-stacked leaves per shard: the plan's
        split dim shifts by the slot axis."""
        S = self.plan.num_shards
        out: List[dict] = [{} for _ in range(S)]
        if len(leaves) != len(self.plan.leaves):
            raise ValueError(
                f"shard plan covers {len(self.plan.leaves)} leaves but "
                f"the wave stack has {len(leaves)}")
        for lp, arr in zip(self.plan.leaves, leaves):
            if tuple(arr.shape) != (wave,) + lp.shape:
                raise ValueError(
                    f"wave leaf {lp.index} ({lp.path}) has shape "
                    f"{arr.shape}; expected {(wave,) + lp.shape}")
            key = _leaf_key(lp.index)
            if lp.mode == "split":
                n = lp.shape[lp.dim] // S
                for s in range(S):
                    idx = [slice(None)] * arr.ndim
                    idx[lp.dim + 1] = slice(s * n, (s + 1) * n)
                    out[s][key] = arr[tuple(idx)]
            else:
                out[lp.owner][key] = arr
        return out

    def finalize(self, step):
        """Close the round: per shard, ``acc/wsum (+ noise)`` — one XLA
        program or ONE fused Pallas launch per shard — then an exact
        host join back to the full tree."""
        if self.count == 0:
            raise RuntimeError("finalize() with no folded uploads; the "
                               "caller must skip aggregation on an "
                               "empty round")
        t0 = time.perf_counter()
        out_slices = []
        # host scalars: every shard's finalize jit sees its own
        # committed acc/reference plus uncommitted wsum/step (a
        # committed default-device wsum would mix placements)
        wsum = np.asarray(self._wsum, np.float32)
        step32 = np.int32(step)
        for s in range(self.plan.num_shards):
            out = self._finalize_fns[s](self._acc[s], wsum,
                                        self._reference[s], step32)
            if self.fused:
                self._c_fused.inc()
            out_slices.append({_shard_key(s): out})
        self._acc = None
        self._wsum = None
        host_slices = [
            {_shard_key(s): {k: np.asarray(v)
                       for k, v in sl[_shard_key(s)].items()}}
            for s, sl in enumerate(out_slices)]
        leaves = self.plan.join_slices(host_slices)
        self._h_finalize.observe(time.perf_counter() - t0)
        return jax.tree.unflatten(self._treedef, leaves)

    # -- crash consistency (utils/journal.py) --------------------------------
    def state_dict(self, include_reference: bool = False) -> dict:
        """`StreamingAggregator.state_dict` twin: the SHARDED
        accumulator as one flat host leaf list (shard-major, slice-key
        order), plus the plan fingerprint so a resume refuses to restore
        into a different layout.  Bit-exact: pieces round-trip through
        numpy in their own acc dtype, ``wsum`` stays f32."""
        if include_reference:
            raise ValueError("the sharded spine does not snapshot the "
                             "reference (edge actors are not sharded)")
        acc = None
        if self._acc is not None:
            acc = []
            for body in self._acc:
                for k in sorted(body):
                    acc.append(np.asarray(body[k]))
        return {
            "acc": acc,
            "wsum": (np.float32(0.0) if self._wsum is None
                     else np.asarray(self._wsum, np.float32)[()]),
            "count": int(self.count),
            "weight_total": float(self.weight_total),
            "shard_fp": int(self.plan.fingerprint())}

    def load_state_dict(self, state: dict) -> None:
        if self._reference is None:
            raise RuntimeError("load_state_dict before reset(): the "
                               "round's clip reference is not set")
        snap_fp = state.get("shard_fp")
        if snap_fp is not None and int(snap_fp) != \
                int(self.plan.fingerprint()):
            raise ValueError(
                "journal snapshot was taken under a DIFFERENT shard "
                "plan (fingerprint mismatch — --model_shards or the "
                "model changed since the crash); restoring it would "
                "fold state into the wrong slots")
        if snap_fp is None and state.get("acc") is not None:
            raise ValueError(
                "journal snapshot carries no shard-plan fingerprint "
                "(it was taken by the replicated fold); the sharded "
                "spine refuses to restore it")
        if state.get("acc") is not None:
            flat = [np.asarray(a) for a in state["acc"]]
            pos = 0
            acc = []
            for s, ref in enumerate(self._reference):
                body = {}
                for k in sorted(ref):
                    body[k] = flat[pos]
                    pos += 1
                acc.append(self._place(s, body))
            if pos != len(flat):
                raise ValueError(
                    f"snapshot holds {len(flat)} accumulator pieces but "
                    f"the plan expects {pos}")
            self._acc = acc
            self._wsum = jnp.float32(state["wsum"])
        self.count = int(state["count"])
        self.weight_total = float(state["weight_total"])
