"""Device & compile observatory: the XLA-level third of the flight
recorder (ROADMAP item 5b — the perf trajectory has host RSS and phase
wall-times but is blind to the layer where the work actually runs).

Three instruments, riding the `PerfRecorder` round cadence (one
``device`` section per ``perf.jsonl`` line on BOTH live servers):

* **per-device memory watermarks** — ``device.memory_stats()`` where the
  backend provides it (TPU/GPU: bytes_in_use / peak / limit), a
  CPU-honest fallback that sums ``jax.live_arrays()`` nbytes where it
  doesn't, and ``null`` where neither is measurable — never a
  fabricated 0, matching the PR 6 ``rss: null`` contract.  This is the
  headroom signal ROADMAP items 1/3 (mega-cohort vmapping, sharded
  global model) cannot be built safely without.
* **a named compile ledger** — every registered hot jit (the defended
  aggregate, the stream fold, the instrumented train fn) records the
  wall time of each call that grew its jit cache, keyed by function
  name and the arg shape/dtype signature that paid the compile.  The
  `RecompileSentry` reads the same signatures, so a recompile warning
  NAMES the arg that changed instead of reporting a bare count
  (FedJAX's lesson, arXiv 2108.02117: vmapped client simulation lives
  or dies on compile-cache discipline).
* **achieved-FLOP/s + an honest MFU gauge** — XLA ``cost_analysis()``
  FLOPs of the registered hot functions, summed per round and quoted
  against ONE peak-FLOPS table (`peak_tflops_for_device` /
  `compiled_flops`).  `trend._validate_device_section` refuses a ledger
  line whose ``mfu`` is above 1.0.

Honesty contract (the retracted-mfu-1.57 lesson):

* an unmeasurable quantity ledgers ``null``, never 0;
* MFU's denominator is the shared device-kind peak table.  The CPU
  backend has no entry and no peak: ``peak_tflops`` and ``mfu`` ledger
  ``null`` there, with the reason in ``peak_source``.  An ACCELERATOR
  whose ``device_kind`` is not in the table is an error, never a default;
* FLOPs whose cost analysis failed mark the round ``flops_complete:
  false`` (the reported sum is then a lower bound — and so is the MFU).

Cost analysis compiles a throwaway twin of each NEW (fn, signature)
cache entry (the same discipline as ``bench._honest_flops`` twins); the
price is one extra compile per entry, paid once, off the steady-state
round path.  Like the rest of ``obs/`` this module is stdlib-only at
import time — jax loads lazily inside the probes.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from fedml_tpu.obs import telemetry

log = logging.getLogger(__name__)

# bf16 dense peak by TPU generation (public spec sheets); matched as a
# substring of jax's device_kind.
PEAK_TFLOPS_BY_KIND = (("v6", 918.0), ("trillium", 918.0), ("v5p", 459.0),
                       ("v5e", 197.0), ("v5lite", 197.0), ("v4", 275.0),
                       ("v3", 123.0), ("v2", 45.0))

MFU_PROVENANCE = ("xla_cost_analysis_of_registered_hot_jits / "
                  "shared_device_kind_peak_table")


def peak_and_source(dev) -> Tuple[Optional[float], str]:
    """``(peak bf16 TF/s, where it came from)`` for ``dev`` — the source
    is ledgered beside every MFU so an impossible value is attributable
    to its denominator, and a null one to its reason."""
    env = os.environ.get("BENCH_PEAK_TFLOPS")
    if env:
        return float(env), "BENCH_PEAK_TFLOPS env override"
    if getattr(dev, "platform", None) == "cpu":
        return None, ("cpu backend: no accelerator peak, so no MFU "
                      "(null, never a number against an assumed chip)")
    kind = str(getattr(dev, "device_kind", "")).lower().replace(" ", "")
    for key, peak in PEAK_TFLOPS_BY_KIND:
        if key in kind:
            return peak, f"device_kind table ({key})"
    raise ValueError(
        f"no peak-FLOPS entry for accelerator device_kind "
        f"{getattr(dev, 'device_kind', None)!r}: add it to "
        f"PEAK_TFLOPS_BY_KIND with its source (or set BENCH_PEAK_TFLOPS) "
        f"— an unknown device is an error, not a default")


def peak_tflops_for_device(dev) -> Optional[float]:
    """Peak bf16 TF/s for ``dev``: the env override, else the table
    entry for its ``device_kind``; None on the CPU backend; an
    accelerator kind the table lacks RAISES.  THE peak table —
    ``bench._peak_for_device`` is this function (identity-pinned)."""
    return peak_and_source(dev)[0]


def compiled_flops(jitted, *args, **kwargs) -> float:
    """XLA's FLOP estimate for the compiled program (0 if unavailable).
    THE cost-analysis probe — ``bench._compiled_flops`` is this function
    (identity-pinned)."""
    try:
        cost = jitted.lower(*args, **kwargs).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        return float(cost.get("flops", 0.0))
    except Exception:  # noqa: BLE001 — absent analysis reads as 0
        return 0.0


# ---------------------------------------------------------------------------
# call signatures (the jit cache key's observable projection)
# ---------------------------------------------------------------------------

def call_signature(args, kwargs=None) -> Tuple[tuple, ...]:
    """Flat shape/dtype tokens for a call's arguments — the observable
    projection of the jit cache key, so two calls with equal signatures
    hit one cache entry and a signature CHANGE names what retraced.

    Tokens are raw ``(dtype_name, shape)`` tuples, NOT strings: this
    runs on the per-upload receive path (every stream fold), so the
    human-readable rendering is deferred to `format_signature` /
    `signature_diff`, which only run when a compile or a verdict
    actually happens.  Python scalars token by TYPE only: jit traces
    them as weak-typed rank-0 arrays, so their VALUE does not key the
    cache — the live servers pass ``round_idx`` as a plain int every
    round, and a value-bearing token would mint a fresh "cache key"
    (and a fresh cost-analysis twin compile) per round for a program
    that never retraced."""
    import jax
    leaves = jax.tree_util.tree_leaves((args, kwargs or {}))
    toks = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            toks.append((str(getattr(dtype, "name", dtype)),
                         tuple(int(d) for d in shape)))
        elif isinstance(leaf, (bool, int, float, complex)):
            toks.append((type(leaf).__name__, ()))
        else:
            toks.append((f"{type(leaf).__name__}={leaf!r}"[:32], None))
    return tuple(toks)


def _format_token(tok) -> str:
    if isinstance(tok, str):  # pre-rendered token (external callers)
        return tok
    name, shape = tok
    if shape is None:
        return name
    return f"{name}[{','.join(str(d) for d in shape)}]"


def format_signature(sig) -> str:
    return ",".join(_format_token(t) for t in sig)


def signature_diff(prev, cur, max_parts: int = 4) -> str:
    """Human-readable diff between two call signatures, naming each leaf
    whose shape/dtype changed (the actionable half of a recompile
    warning)."""
    if prev is None or cur is None:
        return ""
    prev, cur = tuple(prev), tuple(cur)
    parts = []
    if len(prev) != len(cur):
        parts.append(f"arg arity {len(prev)} -> {len(cur)} leaves")
    for i, (a, b) in enumerate(zip(prev, cur)):
        if a != b:
            parts.append(f"arg leaf[{i}]: {_format_token(a)} -> "
                         f"{_format_token(b)}")
    if len(parts) > max_parts:
        parts = parts[:max_parts] + [f"... {len(parts) - max_parts} more"]
    return "; ".join(parts)


def _abstractify(args, kwargs):
    """ShapeDtypeStruct twins of a call's arguments, captured BEFORE the
    call — donation-safe (a donated buffer is unusable afterwards, but
    its shape/dtype twin lowers fine)."""
    import jax

    def leaf(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    return (jax.tree.map(leaf, args), jax.tree.map(leaf, kwargs or {}))


# ---------------------------------------------------------------------------
# per-device memory
# ---------------------------------------------------------------------------

def _live_bytes_by_device() -> Dict[int, int]:
    """Sum of live jax array nbytes per device id (the CPU-honest
    fallback: the CPU backend exposes no allocator stats, but the arrays
    jax holds alive are exactly its device working set).  Sharded arrays
    split their footprint evenly across their devices."""
    import jax
    totals: Dict[int, int] = {}
    for a in jax.live_arrays():
        try:
            devs = list(a.devices())
            nbytes = int(a.nbytes)
        except Exception:  # noqa: BLE001 — array mid-deletion
            continue
        if not devs:
            continue
        share = nbytes // len(devs)
        for d in devs:
            totals[d.id] = totals.get(d.id, 0) + share
    return totals


def device_memory_snapshot() -> Optional[List[dict]]:
    """Per-device memory, best honest source first: ``memory_stats()``
    where the backend provides it, the live-arrays sum where it doesn't,
    and **None** when neither is measurable — the ledger then carries
    ``memory: null``, never a fabricated 0 (the PR 6 contract)."""
    try:
        import jax
        devs = jax.local_devices()
    except Exception:  # noqa: BLE001 — no backend at all
        return None
    if not devs:
        return None
    live = None
    out = []
    for d in devs:
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — backend without the API
            stats = None
        entry = {"id": int(d.id), "platform": str(d.platform),
                 "kind": str(getattr(d, "device_kind", "unknown"))}
        if stats:
            in_use = stats.get("bytes_in_use")
            limit = stats.get("bytes_limit")
            entry.update(
                source="memory_stats",
                bytes_in_use=None if in_use is None else int(in_use),
                peak_bytes=(int(stats["peak_bytes_in_use"])
                            if stats.get("peak_bytes_in_use") is not None
                            else None),
                bytes_limit=None if limit is None else int(limit))
            if in_use is not None and limit:
                entry["utilization"] = float(in_use) / float(limit)
            out.append(entry)
            continue
        if live is None:
            try:
                live = _live_bytes_by_device()
            except Exception:  # noqa: BLE001
                live = {}
        if d.id in live:
            entry.update(source="live_arrays",
                         bytes_in_use=int(live[d.id]),
                         peak_bytes=None, bytes_limit=None)
            out.append(entry)
    return out or None


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------

class DeviceRecorder:
    """Round-cadence device/compile accounting behind `PerfRecorder`.

    ``instrument(name, fn)`` wraps a hot jitted callable: each call is
    signature-tagged (fed to the sentry so a recompile warning names the
    changed arg), calls that grow the jit cache land in the round's
    compile ledger with their wall time, and every call's cost-analysis
    FLOPs accumulate into the round total the MFU gauge is computed
    from.  The wrapper forwards ``_cache_size`` so sentry registration
    keeps working through it.

    Thread-safety: folds/admissions run on receive threads while the
    round closes on the event loop — all round state is lock-guarded.
    Telemetry (per the PR 8 naming rule): non-monotonic measurements
    wear ``_bytes``/``_ratio``/``_value``, never a fake ``_total``;
    ``fedml_dev_compiles_total`` is the one true counter here.
    """

    def __init__(self, registry=None, cost_analysis: bool = True,
                 peak_tflops: Optional[float] = None):
        reg = registry if registry is not None else telemetry.get_registry()
        self._registry = reg
        self.cost_analysis = cost_analysis
        self._lock = threading.Lock()
        self._peak_tflops = peak_tflops
        self._peak_source = ("explicit peak_tflops argument"
                             if peak_tflops is not None else None)
        self._backend: Optional[str] = None
        # lifetime state; a None flops value is an in-flight reservation
        # (another thread is computing the cost-analysis twin)
        self._flops: Dict[Tuple[str, tuple], Optional[float]] = {}
        self._seen_sigs: Dict[str, set] = {}
        self._compile_sizes: Dict[str, set] = {}  # cache sizes observed
        #                                           THIS ROUND per fn
        #                                           (dedupes concurrent
        #                                           first-call
        #                                           observations; reset
        #                                           each round so a
        #                                           post-clear recompile
        #                                           in a later round
        #                                           still ledgers)
        # round state
        self._round_compiles: List[dict] = []
        self._round_calls: Dict[str, int] = {}
        self._round_flops = 0.0
        self._round_flops_complete = True
        self._round_mem_peak: Dict[int, int] = {}
        # telemetry handles, ALL created lazily on first measurement: a
        # gauge registered at construction time would export a
        # fabricated 0.0 for a quantity never measured (the SLO
        # evaluator reads an absent gauge as None — vacuously healthy —
        # and must keep doing so until a real utilization exists)
        self._c_compiles: Dict[str, object] = {}
        self._h_compile: Dict[str, object] = {}
        self._g_mem: Dict[Tuple[int, str], object] = {}
        self._g_util = self._g_flops = self._g_mfu = None

    # -- peak / backend resolution (lazy: jax must not load at import) -------
    def _resolve_peak(self) -> None:
        if self._peak_source is not None:
            return
        import jax
        devs = jax.local_devices()
        n = len(devs)
        self._backend = jax.default_backend()
        # the achieved-FLOP/s numerator sums programs across ALL local
        # devices, so the denominator is the per-chip table peak TIMES
        # the local device count — a sharded aggregate honestly beating
        # one chip's peak must not ledger as "physically impossible"
        peak, source = peak_and_source(devs[0])
        self._peak_tflops = None if peak is None else peak * n
        self._peak_source = source + (
            f" x {n} local devices" if peak is not None and n > 1 else "")

    def backend(self) -> str:
        if self._backend is None:
            import jax
            self._backend = jax.default_backend()
        return self._backend

    # -- instrumentation -----------------------------------------------------
    def instrument(self, name: str, fn: Callable, sentry=None,
                   sentry_name: Optional[str] = None) -> Callable:
        """Wrap a hot (typically jit'd) callable with compile-ledger +
        FLOPs accounting; returns the callable to use in its place.
        ``sentry``: a `RecompileSentry` — every call's signature is noted
        there so the sentry's recompile verdict can name the arg
        shape/dtype that changed.  ``sentry_name``: the name the fn is
        REGISTERED under when it differs from the ledger label (the
        streaming aggregator registers itself as ``stream_agg[rule]``
        while its hot fold ledgers as ``stream_fold[rule]``) — signatures
        must land under the registered name or the verdict diff never
        finds them."""
        probe = getattr(fn, "_cache_size", None)
        lowerable = hasattr(fn, "lower")
        note_as = sentry_name or name
        with self._lock:
            self._seen_sigs.setdefault(name, set())

        def wrapped(*args, **kwargs):
            sig = call_signature(args, kwargs)
            if sentry is not None:
                sentry.note_signature(note_as, sig)
            key = (name, sig)
            abstract = None
            if self.cost_analysis and lowerable:
                with self._lock:
                    # reserve the key BEFORE calling: concurrent first
                    # calls (threaded silo drive, round 0) must pay ONE
                    # cost-analysis twin compile, not one per thread
                    if key not in self._flops:
                        self._flops[key] = None  # in-flight
                        abstract = _abstractify(args, kwargs)
            before = None
            if probe is not None:
                try:
                    before = int(probe())
                except Exception:  # noqa: BLE001 — fn mid-teardown
                    pass
            t0 = time.perf_counter()
            try:
                out = fn(*args, **kwargs)
            except BaseException:
                if abstract is not None:
                    # drop the unfilled reservation: a transient failure
                    # on the FIRST call must not disable cost analysis
                    # for this signature forever
                    with self._lock:
                        if self._flops.get(key) is None:
                            self._flops.pop(key, None)
                raise
            # compile detection: cache growth where the probe exists,
            # first-sight-of-signature where it doesn't
            compiled = sig not in self._seen_sigs[name]
            if probe is not None and before is not None:
                try:
                    compiled = int(probe()) > before
                except Exception:  # noqa: BLE001
                    pass
            if compiled:
                # block before timing: a compile's wall time must not be
                # hidden behind async dispatch
                try:
                    import jax
                    jax.block_until_ready(out)
                except Exception:  # noqa: BLE001
                    pass
            dt = time.perf_counter() - t0
            # cost analysis AFTER the timed call (a throwaway twin
            # compile — once per new (fn, signature) entry, never again)
            flops = None
            if abstract is not None:
                flops = compiled_flops(fn, *abstract[0], **abstract[1])
            self._note_call(name, sig, dt, compiled, probe, flops)
            return out

        if probe is not None:
            wrapped._cache_size = probe
        wrapped.__wrapped__ = fn
        wrapped.__name__ = getattr(fn, "__name__", name)
        return wrapped

    def _note_call(self, name, sig, dt, compiled, probe, flops) -> None:
        size = None
        if compiled and probe is not None:
            try:
                size = int(probe())
            except Exception:  # noqa: BLE001
                pass
        with self._lock:
            self._seen_sigs.setdefault(name, set()).add(sig)
            self._round_calls[name] = self._round_calls.get(name, 0) + 1
            key = (name, sig)
            if flops is not None and self._flops.get(key) is None:
                self._flops[key] = flops  # fill the in-flight reservation
            known = self._flops.get(key)
            if known is not None and known > 0:
                self._round_flops += known
            else:
                self._round_flops_complete = False
            if compiled and size is not None:
                # concurrent first calls both observe "cache grew to N"
                # for ONE real entry (jax compiles once under its own
                # lock; the loser's wall time is lock-wait, not a
                # compile) — only the first observation of each cache
                # size per fn per ROUND is a compile event.  A genuine
                # same-shape double compile (the numpy-vs-jax round-0
                # class) grows the cache to a NEW size and still
                # records; an explicit cache clear re-compiling in a
                # later round records too (the set resets at
                # round_start).
                seen = self._compile_sizes.setdefault(name, set())
                if size in seen:
                    compiled = False
                else:
                    seen.add(size)
            if compiled:
                entry = {"fn": name, "wall_s": round(dt, 6),
                         "signature": format_signature(sig)}
                if size is not None:
                    entry["cache_size"] = size
                if known is not None:
                    entry["flops"] = known
                self._round_compiles.append(entry)
        if compiled:
            c = self._c_compiles.get(name)
            if c is None:
                c = self._registry.counter("fedml_dev_compiles_total",
                                           fn=name)
                self._c_compiles[name] = c
            c.inc()
            h = self._h_compile.get(name)
            if h is None:
                h = self._registry.histogram("fedml_dev_compile_seconds",
                                             fn=name)
                self._h_compile[name] = h
            h.observe(dt)

    # -- memory --------------------------------------------------------------
    def sample_memory(self) -> Optional[List[dict]]:
        """One memory snapshot, folded into the round's per-device
        watermark (callers may sample mid-round; `round_start` /
        `round_snapshot` each take one)."""
        snap = device_memory_snapshot()
        if snap:
            with self._lock:
                for e in snap:
                    b = e.get("bytes_in_use")
                    if b is None:
                        continue
                    if b > self._round_mem_peak.get(e["id"], -1):
                        self._round_mem_peak[e["id"]] = b
        return snap

    # -- round lifecycle -----------------------------------------------------
    def round_start(self) -> None:
        with self._lock:
            self._round_compiles = []
            self._round_calls = {}
            self._round_flops = 0.0
            self._round_flops_complete = True
            self._round_mem_peak = {}
            self._compile_sizes = {}
        self.sample_memory()

    def round_snapshot(self, round_s: Optional[float]) -> dict:
        """Close the round: one ledger-ready ``device`` section.  Every
        unmeasurable quantity is ``null`` — never 0."""
        self._resolve_peak()
        mem = self.sample_memory()
        with self._lock:
            compiles = list(self._round_compiles)
            calls = dict(self._round_calls)
            flops = self._round_flops
            complete = self._round_flops_complete
            peaks = dict(self._round_mem_peak)
        if mem:
            for e in mem:
                if e["id"] in peaks:
                    e["round_peak_bytes"] = peaks[e["id"]]
        achieved = mfu = None
        if flops > 0 and round_s:
            achieved = flops / float(round_s)
            if self._peak_tflops is not None:
                mfu = achieved / (self._peak_tflops * 1e12)
        section = {
            "backend": self.backend(),
            "memory": mem,
            "compiles": compiles,
            "jit_calls": calls,
            "flops": flops if flops > 0 else None,
            "achieved_flops_per_s": achieved,
            "mfu": mfu,
            "peak_tflops": self._peak_tflops,
            "peak_source": self._peak_source,
            "mfu_provenance": MFU_PROVENANCE,
        }
        if calls:
            section["flops_complete"] = complete
        # gauges: set only what was measured (an absent gauge reads as
        # None downstream — the SLO evaluator treats it as vacuous)
        for e in mem or []:
            for field, label in (("bytes_in_use", "in_use"),
                                 ("round_peak_bytes", "peak")):
                v = e.get(field)
                if v is None:
                    continue
                gkey = (e["id"], label)
                g = self._g_mem.get(gkey)
                if g is None:
                    # literal names: the source-scan metric lint
                    # (tests/test_metric_naming.py) pins these series
                    if label == "in_use":
                        g = self._registry.gauge(
                            "fedml_dev_mem_in_use_bytes",
                            device=str(e["id"]))
                    else:
                        g = self._registry.gauge(
                            "fedml_dev_mem_peak_bytes",
                            device=str(e["id"]))
                    self._g_mem[gkey] = g
                g.set(v)
        utils = [e["utilization"] for e in mem or [] if "utilization" in e]
        if utils:
            if self._g_util is None:
                self._g_util = self._registry.gauge(
                    "fedml_dev_mem_utilization_ratio")
            self._g_util.set(max(utils))
        if achieved is not None:
            if self._g_flops is None:
                self._g_flops = self._registry.gauge(
                    "fedml_dev_achieved_flops_value")
            self._g_flops.set(achieved)
        if mfu is not None:
            if self._g_mfu is None:
                self._g_mfu = self._registry.gauge("fedml_perf_mfu_ratio")
            self._g_mfu.set(mfu)
        return section
