"""The defended aggregate: one jit for clip + noise + Byzantine rule.

Following FedJAX's one-XLA-program aggregation discipline, the whole
screen-survivors → defend → aggregate step compiles ONCE: the server
stacks the round's admitted uploads into the static ``[N, ...]`` cohort
shape (quarantined / rejected / dropped slots hold a copy of the global
with weight 0 — masked, never gathered out, so shapes never depend on
who showed up), and this module's jitted function does the rest:

1. **norm-diff clipping** (reference parity,
   ``fedml_core/robustness/robust_aggregation.py:38-49``) — each slot's
   update is clipped to ``norm_clip`` via `core.robust.clip_update`
   vmapped over the cohort axis;
2. **aggregation** — plain ``tree_weighted_mean`` or any
   `core/byzantine.py` rule (coordinate_median / trimmed_mean / krum /
   multi_krum / geometric_median), all of which honor weight-0 slots;
3. **weak-DP noise** (reference parity, ``:51-55``) — seeded Gaussian
   noise on the aggregate, folded per round so every round's draw is
   fresh but the run replays deterministically.

The async server reuses the same function on its ``[goal, ...]`` delta
buffer with a zeros reference tree (clipping a delta against zero IS
norm clipping the delta) and applies the staleness discount to the
robust aggregate afterwards — screen before buffering, discount after.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from fedml_tpu.core.byzantine import METHODS, make_byzantine_aggregate
from fedml_tpu.core.pytree import acc_dtype
from fedml_tpu.core.robust import add_gaussian_noise, clip_update

ROBUST_AGG_METHODS = ("mean",) + METHODS


def make_defended_aggregate(method: str = "mean", *, trim_frac: float = 0.1,
                            byz_f: int = 0, krum_m: int = 1,
                            gm_iters: int = 8, gm_eps: float = 1e-6,
                            norm_clip: float = 0.0, noise_std: float = 0.0,
                            seed: int = 0, sentry=None,
                            device=None) -> Callable:
    """Build the jitted ``fn(global_params, stacked, weights, step) ->
    new_params`` the server actors call once per round/version.

    ``stacked``: the static ``[N, ...]`` cohort tree (weight-0 slots are
    copies of ``global_params`` for the sync path / zeros for deltas).
    ``weights``: ``[N]`` raw sample counts, 0 for masked slots —
    callers must guard the all-zero cohort (skip aggregation) before
    calling.  ``step`` seeds the per-round noise fold; it traces as a
    scalar, so varying it never recompiles.  The returned function is a
    single jit — tests pin ``fn._cache_size() == 1`` after a full run
    (no per-round recompiles, the acceptance criterion).

    Nothing is donated: XLA honours a donation only by aliasing it to
    an output of the same shape, and none has the ``[N, ...]`` shape.

    ``sentry``: a `fedml_tpu.obs.perf.RecompileSentry`; when set, the
    returned jit registers itself, so the flight recorder counts (and
    under strict mode fails) any round that grows its cache — the
    ``_cache_size() == 1`` acceptance criterion, enforced live instead
    of only in tests.

    ``device``: a `fedml_tpu.obs.device.DeviceRecorder`; when set, the
    returned callable is the observatory's wrapper — each compile lands
    in the round's named compile ledger with its wall time and arg
    signature, every call's cost-analysis FLOPs feed the live MFU
    gauge, and the sentry's recompile verdicts can name the arg
    shape/dtype that changed.  The wrapper forwards ``_cache_size``, so
    the jit-once pin holds with it on or off.
    """
    if method not in ROBUST_AGG_METHODS:
        raise ValueError(f"unknown robust aggregation method {method!r}; "
                         f"available: {ROBUST_AGG_METHODS}")
    if norm_clip < 0 or noise_std < 0:
        raise ValueError(f"norm_clip/noise_std must be >= 0, got "
                         f"{norm_clip}/{noise_std}")
    if method == "mean":
        base = None  # fused clip + sequential fold below
    else:
        base = make_byzantine_aggregate(method, trim_frac=trim_frac,
                                        byz_f=byz_f, krum_m=krum_m,
                                        gm_iters=gm_iters, gm_eps=gm_eps)

    def _scan_mean(global_params, stacked, weights):
        """Clip + weighted mean as a sequential cohort-order `lax.scan`
        — arithmetically the SAME per-slot fold
        `core.stream_agg.StreamingAggregator` runs at upload arrival,
        so stream and stack modes agree BIT FOR BIT when uploads fold
        in slot order (weight-0 slots hold the reference and contribute
        an exact ``+0.0``).  fp addition is order-sensitive, so this is
        deliberately NOT the fused ``jnp.sum`` of `tree_weighted_mean`:
        a vectorized reduce uses a different summation tree and the two
        modes would differ in the last ulp forever."""
        acc0 = jax.tree.map(
            lambda r: jnp.zeros(jnp.shape(r), acc_dtype(jnp.asarray(r).dtype)),
            global_params)

        def body(carry, slot):
            acc, tot = carry
            upd, w = slot
            if norm_clip > 0:
                upd = clip_update(upd, global_params, norm_clip)
            acc = jax.tree.map(
                lambda a, u: a + u.astype(a.dtype) * w.astype(a.dtype),
                acc, upd)
            return (acc, tot + w), None

        (acc, tot), _ = jax.lax.scan(body, (acc0, jnp.float32(0.0)),
                                     (stacked, weights))
        return jax.tree.map(
            lambda a, r: (a / tot.astype(a.dtype)).astype(
                jnp.asarray(r).dtype), acc, global_params)

    def _aggregate(global_params, stacked, weights, step):
        weights = jnp.asarray(weights, jnp.float32)
        if base is None:
            out = _scan_mean(global_params, stacked, weights)
        else:
            if norm_clip > 0:
                stacked = jax.vmap(
                    lambda c: clip_update(c, global_params,
                                          norm_clip))(stacked)
            out = base(stacked, weights)
        if noise_std > 0:
            key = jax.random.fold_in(jax.random.key(seed),
                                     jnp.asarray(step, jnp.uint32))
            out = add_gaussian_noise(out, key, noise_std)
        return out

    fn = jax.jit(_aggregate)
    if sentry is not None:
        sentry.register(f"defended_aggregate[{method}]", fn)
    if device is not None:
        fn = device.instrument(f"defended_aggregate[{method}]", fn,
                               sentry=sentry)
    return fn
