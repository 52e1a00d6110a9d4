"""Continuous-batching decode scheduler for autoregressive serving.

The `MicroBatcher` pads requests into a bucket, runs ONE forward, and
drains the whole batch — correct for one-shot models, but an
autoregressive sequence is hundreds of steps long and sequences finish
at different times: pad-to-bucket decode drains to occupancy ~1 while
one long sequence finishes, wasting most of the accelerator.  This
module schedules the way production LLM servers do (continuous
batching): ONE persistent compiled decode step over a fixed
``[slots]`` batch, where a finished sequence vacates its slot at the
end of a step and a queued request joins the free slot at the start of
the next — admission happens per STEP, not per batch, so occupancy
stays near capacity under backlog.

The compiled step is `TransformerLM`'s incremental decode: per-layer KV
caches as explicit carried state (`models.transformer.init_decode_cache`),
donated in place every step.  Shapes are fully static — ``[slots]``
tokens, ``[slots]`` positions, ``[slots, cache_len, ...]`` caches — so
the whole serving lifetime is ONE jit cache entry per (slots,
cache-bucket) pair; the scheduler exposes ``_cache_size`` and registers
with the PR 9 `RecompileSentry`/compile ledger so a retrace on the
decode hot path is named, never silent.  Prompts are consumed through
the same step (one prompt token per step, logits ignored until the last
one) — slower than a dedicated prefill program for long prompts, but it
keeps the one-entry compile contract and prompt tokens interleave with
other slots' decode steps instead of stalling them.

Model-version consistency (the registry's torn-read contract, extended
in time): a KV cache computed under version v is NOT valid state for
version v+1, so a hot swap must never land mid-sequence.  The scheduler
pins one `ServedModel` snapshot while any slot is live; when the
registry moves on, it stops ADMITTING (a swap barrier) and lets live
sequences finish on the pinned version — bounded by ``max_new`` steps —
then swaps and resumes.  Every result carries the version that decoded
ALL of its tokens.

``continuous=False`` is the drain-per-batch baseline the bench compares
against: admission only when every slot is free, exactly the
pad-to-bucket discipline, kept as a first-class mode so the occupancy
claim is measured against the real alternative, not a strawman.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from concurrent.futures import Future
from typing import List, Optional

import numpy as np

from fedml_tpu.obs import telemetry, trace
from fedml_tpu.serve.batcher import (SHED_REASONS, TIERS, ShedError,
                                     TierAdmission, _settle,
                                     best_effort_cap)

log = logging.getLogger(__name__)


class DecodeResult:
    """One finished sequence: the generated token ids, the model version
    that produced EVERY one of them (the swap barrier guarantees a
    single version per sequence), and whether generation was cut by the
    cache bucket rather than max_new/EOS."""
    __slots__ = ("tokens", "version", "truncated")

    def __init__(self, tokens: List[int], version: int, truncated: bool):
        self.tokens = tokens
        self.version = version
        self.truncated = truncated


class _DecodeRequest:
    __slots__ = ("prompt", "max_new", "deadline", "enq_t", "future",
                 "tier", "capped", "ctx")

    def __init__(self, prompt, max_new, deadline, enq_t, future, tier,
                 capped=False, ctx=None):
        self.prompt = prompt
        self.max_new = max_new
        self.deadline = deadline
        self.enq_t = enq_t
        self.future = future
        self.tier = tier
        self.capped = capped   # max_new was cut at admission to fit the
        #                        cache bucket: the result is `truncated`
        self.ctx = ctx         # submitter's span context, if any


class _Slot:
    """Host-side state of one in-flight sequence."""
    __slots__ = ("req", "pos", "generated")

    def __init__(self, req: _DecodeRequest):
        self.req = req
        self.pos = 0          # next sequence index to feed
        self.generated: List[int] = []

    def next_token(self) -> int:
        if self.pos < len(self.req.prompt):
            return int(self.req.prompt[self.pos])
        return self.generated[-1]


class DecodeScheduler:
    """Continuous-batching greedy decode over a fixed-slot compiled step.

    ``registry``: a `ModelRegistry` whose published params belong to
    ``model`` (a `TransformerLM`); the registry's ``apply_fn`` is not
    used here — the scheduler compiles its own decode step.
    ``slots``: the fixed batch width; ``cache_len``: the KV cache bucket
    (prompt + generated tokens must fit; a sequence hitting the wall
    finishes ``truncated``).  ``eos_id``: optional stop token.
    ``continuous``: per-step slot admission (False = drain-per-batch
    baseline).  ``worker``/``slo``/``best_effort_headroom``: the same
    tiered-admission surface as `MicroBatcher`.
    """

    def __init__(self, registry, model, *, slots: int = 8,
                 cache_len: int = 128, queue_depth: int = 256,
                 max_new: int = 32, eos_id: Optional[int] = None,
                 continuous: bool = True,
                 default_deadline_s: Optional[float] = None,
                 worker: Optional[str] = None, slo=None,
                 best_effort_headroom: float = 0.5,
                 cache_dtype=None):
        import jax
        import jax.numpy as jnp

        from fedml_tpu.models.transformer import init_decode_cache
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        self.registry = registry
        self.model = model
        self.slots = slots
        self.cache_len = cache_len
        self.max_new = max_new
        self.eos_id = eos_id
        self.continuous = continuous
        self.default_deadline_s = default_deadline_s
        self.worker = worker
        # captured once (the actor idiom): disabled tracing pays one
        # `is None` branch per step/finish, no lookups on the hot loop
        self._tracer = trace.get_tracer()
        self._q: "queue.Queue" = queue.Queue(maxsize=queue_depth)
        self._slots: List[Optional[_Slot]] = [None] * slots
        self._snapshot = None           # pinned ServedModel
        self._params_dev = None         # device-put params of _snapshot
        self._swap_pending = False
        self._stopped = False
        self._drain = True
        self._thread: Optional[threading.Thread] = None
        self._admit_lock = threading.Lock()
        self._wake = threading.Event()
        # bench-readable occupancy accounting (telemetry-independent)
        self.steps = 0
        self.live_steps = 0             # sum of live slots over steps

        cache_dtype = cache_dtype if cache_dtype is not None \
            else jnp.float32
        self._fresh_cache = lambda: init_decode_cache(
            model, slots, cache_len, dtype=cache_dtype)
        self._cache = None

        def _step(params, cache, tokens, positions):
            logits, cache = model.apply(params, tokens,
                                        positions=positions, cache=cache)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

        # ONE jit entry for the scheduler's lifetime: static [slots]
        # shapes, donated cache.  _cache_size is the sentry probe.
        self._step_jit = jax.jit(_step, donate_argnums=(1,))
        self._step_fn = self._step_jit   # obs instrumentation wraps this

        reg = telemetry.get_registry()
        lbl = {} if worker is None else {"worker": str(worker)}
        self._c_requests = reg.counter("fedml_serve_decode_requests_total",
                                       **lbl)
        self._c_steps = reg.counter("fedml_serve_decode_steps_total",
                                    **lbl)
        self._c_tokens = reg.counter("fedml_serve_decode_tokens_total",
                                     **lbl)
        self._c_swaps = reg.counter("fedml_serve_decode_swaps_total",
                                    **lbl)
        self._adm = TierAdmission(
            {(r, t): reg.counter("fedml_serve_decode_shed_total",
                                 reason=r, tier=t, **lbl)
             for r in SHED_REASONS for t in TIERS},
            slo, best_effort_cap(queue_depth, best_effort_headroom))
        self.tier_gate = self._adm.gate
        self._h_occupancy = reg.histogram(
            "fedml_serve_decode_occupancy_total",
            buckets=tuple(float(i) for i in range(1, slots + 1)), **lbl)
        self._h_request = reg.histogram("fedml_serve_request_seconds",
                                        path="decode", **lbl)
        self._g_util = reg.gauge("fedml_serve_queue_utilization_ratio",
                                 path="decode", **lbl)

    # -- observability -------------------------------------------------------
    def _cache_size(self) -> int:
        """Jit cache entries of the decode step (the sentry probe): must
        stay 1 for the scheduler's lifetime — slot churn, mid-flight
        joins, and swap barriers never change a shape."""
        return int(self._step_jit._cache_size())

    def register_obs(self, recorder=None, sentry=None,
                     name: Optional[str] = None) -> str:
        """Register the decode step with the PR 9 observatory: the
        compile ledger names it ``decode_step[s<slots>,c<cache_len>]``
        and the recompile sentry watches its jit cache.  Returns the
        ledger name."""
        name = name or f"decode_step[s{self.slots},c{self.cache_len}]"
        if sentry is not None:
            sentry.register(name, self)
        if recorder is not None:
            self._step_fn = recorder.instrument(
                name, self._step_jit, sentry=sentry, sentry_name=name)
        return name

    def occupancy(self) -> Optional[float]:
        """Mean live slots per step so far (None before any step)."""
        return self.live_steps / self.steps if self.steps else None

    def depth(self) -> int:
        return self._q.qsize()

    # -- client side ---------------------------------------------------------
    def _shed(self, reason: str, tier: str = "interactive") -> ShedError:
        return self._adm.shed(reason, tier)

    def submit(self, prompt, max_new: Optional[int] = None,
               deadline_s: Optional[float] = None,
               tier: str = "interactive") -> Future:
        """Enqueue one sequence: ``prompt`` is a non-empty list of token
        ids; the Future resolves to a `DecodeResult`.  ``deadline_s``
        bounds QUEUE wait (admission), not generation — once a sequence
        holds a slot it runs to completion.  Sheds exactly like
        `MicroBatcher.submit` (queue_full / deadline-at-admission /
        shutdown / no_model / slo_degraded for best-effort)."""
        self._adm.screen(tier, self._q.qsize())
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt: decode needs >= 1 token")
        max_new = self.max_new if max_new is None else int(max_new)
        capped = False
        if len(prompt) + max_new > self.cache_len:
            # admission-time honesty: the cache bucket cannot hold it —
            # cap max_new here and flag the request, so the result says
            # `truncated` (the generation WAS cut by the bucket, the cut
            # just happened at admission instead of mid-flight; a prompt
            # alone overflowing the bucket is a client error)
            if len(prompt) >= self.cache_len:
                raise ValueError(
                    f"prompt of {len(prompt)} tokens does not fit the "
                    f"cache bucket ({self.cache_len})")
            max_new = self.cache_len - len(prompt)
            capped = True
        if deadline_s is None:
            deadline_s = self.default_deadline_s
        now = time.monotonic()
        ctx = (self._tracer.current_context()
               if self._tracer is not None else None)
        req = _DecodeRequest(
            prompt, max_new,
            None if deadline_s is None else now + deadline_s,
            now, Future(), tier, capped, ctx)
        with self._admit_lock:
            if self._stopped:
                raise self._shed("shutdown", tier)
            try:
                self._q.put_nowait(req)
            except queue.Full:
                raise self._shed("queue_full", tier) from None
        self._c_requests.inc()
        self._note_util()
        self._wake.set()
        return req.future

    def _note_util(self) -> None:
        """Refresh the queue-fill gauge.  Called on submit AND from the
        worker loop after admission — a gauge only written on submit
        would latch a burst's high-water mark forever once traffic
        stops, self-sustaining an SLO breach (and best-effort shedding)
        on an idle instance."""
        if self._q.maxsize > 0:   # maxsize 0 = unbounded: no fill ratio
            self._g_util.set(self._q.qsize() / self._q.maxsize)

    def generate(self, prompt, max_new: Optional[int] = None,
                 deadline_s: Optional[float] = None,
                 timeout: Optional[float] = 60.0,
                 tier: str = "interactive") -> DecodeResult:
        """Blocking submit-and-wait convenience."""
        return self.submit(prompt, max_new, deadline_s,
                           tier=tier).result(timeout)

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "DecodeScheduler":
        if self._thread is None:
            self._thread = threading.Thread(target=self._run, daemon=True,
                                            name="serve-decode")
            self._thread.start()
        return self

    def stop(self, drain: bool = True) -> None:
        """Stop admitting; with ``drain`` finish every in-flight AND
        queued sequence first (bounded by max_new steps each), without
        it shed the queue and fail live slots.  Idempotent.  The worker
        never blocks on the queue (it polls with a bounded wait), so a
        flag + wake is enough — no sentinel needed."""
        with self._admit_lock:
            if self._stopped and self._thread is None:
                return
            self._stopped = True
            self._drain = drain
        self._wake.set()
        if self._thread is None:
            # never started: honor the drain contract inline (the
            # MicroBatcher convention — queued work still gets answers)
            if drain and self._refresh_snapshot():
                self._drain_all()
            self._flush_queue(shed=True)
            return
        self._thread.join(timeout=120)
        if self._thread.is_alive():
            # a drain deeper than the timeout: the worker is STILL
            # stepping — marking it stopped would let a second stop()
            # take the inline-drain path and mutate slots/cache
            # concurrently with the live worker
            log.warning("decode scheduler: worker still draining after "
                        "120s; call stop() again to keep waiting")
            return
        self._thread = None

    def warmup(self) -> bool:
        """Pay the decode-step compile before serving (one all-dead step
        against the live model).  No-op without a published model."""
        if not self._refresh_snapshot(force=True):
            return False
        self._ensure_cache()
        tokens = np.zeros(self.slots, np.int32)
        positions = np.zeros(self.slots, np.int32)
        out, self._cache = self._step_fn(self._params_dev, self._cache,
                                         tokens, positions)
        np.asarray(out)   # block: the compile must land here, not later
        return True

    # -- worker --------------------------------------------------------------
    def _refresh_snapshot(self, force: bool = False) -> bool:
        """Pin the registry's current snapshot (device-putting params
        once).  With live slots a NEWER version only marks the swap
        barrier — the pinned snapshot keeps serving until they drain."""
        import jax
        cur = self.registry.current()
        if cur is None:
            return self._snapshot is not None
        if self._snapshot is None or force \
                or (cur.version != self._snapshot.version
                    and not any(self._slots)):
            swapped = (self._snapshot is not None
                       and cur.version != self._snapshot.version)
            self._snapshot = cur
            self._params_dev = jax.device_put(cur.params)
            self._swap_pending = False
            if swapped:
                self._c_swaps.inc()
        elif cur.version != self._snapshot.version:
            self._swap_pending = True
        return True

    def _ensure_cache(self) -> None:
        if self._cache is None:
            self._cache = self._fresh_cache()

    def _admit(self) -> None:
        """Fill free slots from the queue.  Continuous mode admits into
        any free slot every step; drain mode only refills once EVERY
        slot is free (the pad-to-bucket baseline).  The swap barrier
        blocks all admission until live sequences finish."""
        if self._swap_pending:
            return
        if not self.continuous and any(self._slots):
            return
        now = time.monotonic()
        for i in range(self.slots):
            if self._slots[i] is not None:
                continue
            while True:
                try:
                    req = self._q.get_nowait()
                except queue.Empty:
                    return
                if req.deadline is not None and now > req.deadline:
                    _settle(req.future,
                            exc=self._shed("deadline", req.tier))
                    continue
                self._slots[i] = _Slot(req)
                break

    def _finish(self, i: int, truncated: bool) -> None:
        slot = self._slots[i]
        self._slots[i] = None
        done = time.monotonic()
        self._h_request.observe(done - slot.req.enq_t)
        if self._tracer is not None:
            # one retroactive span per finished sequence, hung under
            # the submitter's request span when it carried one
            self._tracer.record_span(
                "serve_decode", done - slot.req.enq_t,
                parent=slot.req.ctx, tokens=len(slot.generated),
                version=self._snapshot.version, truncated=truncated)
        _settle(slot.req.future,
                DecodeResult(slot.generated, self._snapshot.version,
                             truncated))

    def _step_once(self) -> None:
        live_idx = [i for i, s in enumerate(self._slots) if s is not None]
        if not live_idx:
            return
        tokens = np.zeros(self.slots, np.int32)
        positions = np.zeros(self.slots, np.int32)
        for i in live_idx:
            s = self._slots[i]
            tokens[i] = s.next_token()
            positions[i] = s.pos
        self._ensure_cache()
        t0 = time.perf_counter()
        out, self._cache = self._step_fn(self._params_dev, self._cache,
                                         tokens, positions)
        out = np.asarray(out)
        if self._tracer is not None:
            self._tracer.record_span("decode_step",
                                     time.perf_counter() - t0,
                                     live=len(live_idx))
        self.steps += 1
        self.live_steps += len(live_idx)
        self._c_steps.inc()
        self._c_tokens.inc(len(live_idx))
        self._h_occupancy.observe(len(live_idx))
        for i in live_idx:
            s = self._slots[i]
            feeding_prompt = s.pos < len(s.req.prompt) - 1
            s.pos += 1
            if feeding_prompt:
                # mid-prompt logits predict a token the prompt already
                # pins — ignored (teacher forcing)
                continue
            tok = int(out[i])
            s.generated.append(tok)
            if self.eos_id is not None and tok == self.eos_id:
                self._finish(i, truncated=False)   # a natural stop is
                #          never a truncation, even on a capped request
            elif len(s.generated) >= s.req.max_new:
                self._finish(i, truncated=s.req.capped)
            elif s.pos >= self.cache_len:   # unreachable given the
                # admission cap; kept as belt-and-braces against a
                # future admission change silently overrunning the cache
                self._finish(i, truncated=True)

    def _flush_queue(self, shed: bool, reason: str = "shutdown") -> None:
        while True:
            try:
                req = self._q.get_nowait()
            except queue.Empty:
                return
            if shed:
                _settle(req.future, exc=self._shed(reason, req.tier))

    def _run(self) -> None:
        while True:
            with self._admit_lock:
                stopped = self._stopped
            if stopped:
                break
            if not self._refresh_snapshot():
                # no model yet: requests would wait forever on an empty
                # registry — fail them the way MicroBatcher does
                self._flush_queue(shed=True, reason="no_model")
                self._wake.wait(timeout=0.05)
                self._wake.clear()
                continue
            self._admit()
            self._note_util()
            if not any(self._slots):
                if self._swap_pending:
                    # all sequences drained: complete the barrier swap
                    self._refresh_snapshot()
                    continue
                self._wake.wait(timeout=0.05)
                self._wake.clear()
                continue
            self._step_once()
        # shutdown: drain answers every admitted AND queued sequence
        # (the swap barrier still clears between batches), abort fails
        # them all.  _refresh_snapshot, not a _snapshot check: a stop()
        # racing the worker's FIRST loop iteration must still pin the
        # published model and honor the drain contract
        if self._drain and self._refresh_snapshot():
            self._drain_all()
        for i, s in enumerate(self._slots):
            if s is not None:
                self._slots[i] = None
                _settle(s.req.future,
                        exc=self._shed("shutdown", s.req.tier))
        self._flush_queue(shed=True)

    def _drain_all(self) -> None:
        """Run the step loop until every admitted and queued sequence
        has answered (bounded: each costs <= cache_len steps)."""
        while True:
            self._refresh_snapshot()
            self._admit()
            if not any(self._slots):
                break
            self._step_once()
