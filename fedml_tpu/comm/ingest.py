"""Zero-copy pipelined ingest: aggregation hidden behind the network
(ROADMAP item 4; the Smart-NIC offload analog of arXiv 2307.06561).

Today every upload is decoded, admission-screened, and folded
sequentially on the transport receive thread — the wire stalls while
the host walks trees, and the host stalls while XLA folds.  This module
moves everything heavier than header validation OFF that thread:

* `IngestArena` — a pre-pinned flat float32 staging buffer keyed by the
  shard spec's leaf layout (the wire codec's canonical flatten order,
  `comm/message._flatten_arrays`).  A frame's zero-copy leaf views are
  gathered into the arena (one bounded memcpy per leaf — replacing one
  host→device transfer per leaf) and shipped with ONE ``device_put``
  per shard.  The structural screen compares the frame header's leaf
  descriptors + pytree spec against the template — no tree walk, no
  host materialization — and the finite + sumsq screens run as one
  fused jit reduction over the flat buffer, replacing the per-upload
  host O(model) passes in `robust/admission.py` (consumed through the
  ``pre=`` seam of `AdmissionPipeline.admit` /
  `ShardAdmission.offer`).  The arena and the fused screen each key
  exactly one entry in the compile ledger (`ingest_arena`,
  ``ingest_screen`` — pinned by the bench's 0-recompile gate).

* `IngestPipeline` — bounded per-shard queues with a single-consumer
  fold worker per shard.  The transport thread only validates the
  envelope and enqueues; the worker runs decode → screen → fold, so
  fold order per shard stays the deterministic arrival order and the
  pipelined global is bit-identical to the inline path (the journal's
  durable-prefix recovery contract composes: a kill with frames still
  queued leaves exactly the un-folded silos un-journaled).  Queue
  overflow applies backpressure two ways: ``submit`` (transport path)
  dead-letters the frame through ``fedml_comm_dead_letter_total
  {reason="ingest_overflow"}`` + the resilient-transport ``fault_feed``
  so the drop attributes as a NETWORK fault (never a trust strike);
  ``submit_wait`` (the cross-device wave path — the producer is the
  local wave engine, not a remote silo) blocks the producer instead.

Thread-safety contract: one worker per shard is the whole design —
WITHIN a shard nothing is concurrent, so the fold, the staging buffer,
and the arena need no locks of their own.  Cross-shard shared state
(the silo-granular `ShardAdmission`, the barrier dict) is serialized by
the server actor's ingest lock; the arena stage (gather + device_put +
fused screen) runs OUTSIDE it, which is where the per-shard
parallelism lives.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import math
import queue
import threading
from typing import Any, Callable, List, Optional

import numpy as np

from fedml_tpu.obs import telemetry
from fedml_tpu.obs.critical_path import IngestGauges

log = logging.getLogger(__name__)

_STOP = object()

#: the dead-letter reason ingest overflow books (the `comm/resilient.py`
#: closed set gains it): backpressure drops are NETWORK faults by
#: attribution — the silo's payload was never even looked at
OVERFLOW_REASON = "ingest_overflow"


@dataclasses.dataclass
class ArenaScreen:
    """The arena's precomputed screen results, handed to the admission
    seam (``AdmissionPipeline.admit(pre=...)`` /
    ``ShardAdmission.offer(pre=...)``) so the host O(model) fingerprint
    / finite / norm passes are skipped.  ``tree`` carries the staged
    device leaves in the template's pytree shape — value-identical to
    the frame's host views, so the fold stays bit-identical.

    ``structural_ok=False`` means the frame header did not match the
    template (the admission seam rejects it as ``fingerprint`` damage
    without touching a single payload byte); every other field is then
    meaningless."""
    structural_ok: bool
    finite: bool = False
    sumsq: float = 0.0
    norm: float = 0.0
    tree: Any = None


class IngestArena:
    """Pre-pinned flat float32 staging arena for ONE payload template
    (the whole model, or one shard's slice layout).

    ``template``: the payload pytree this arena stages (the broadcast
    template / the shard plan's slice of it).  Only all-float32
    templates are supported — ``supported`` is False otherwise and the
    caller keeps the host screen path (the pipeline itself still
    applies; masked secagg uploads are uint32 by construction and ride
    host screens).

    Per-round protocol: ``round_start(reference)`` stages the round's
    screen reference (the current global for ``kind="params"`` norms;
    ``None`` keeps a zero reference — the ``kind="delta"`` norm).
    ``stage_message(msg, key)`` / ``stage_tree(tree)`` gather, ship,
    and screen one upload; single-consumer discipline (one arena per
    fold worker) is the caller's contract — the flat buffer is reused
    across uploads."""

    def __init__(self, template, *, name: str = "ingest", perf=None,
                 device=None):
        """``device``: where staged uploads land (a sharded server passes
        each shard's own); None is the default device."""
        import jax
        from fedml_tpu.comm.message import _flatten_arrays
        # host-normalize first: the wire codec ships numpy trees, and
        # _flatten_arrays would file a device array as a "plain" JSON
        # value instead of a leaf
        template = jax.tree.map(np.asarray, template)
        leaves, spec = _flatten_arrays(template)
        leaves = [np.asarray(l) for l in leaves]
        # JSON-normalized spec: the frame header's spec went through
        # json (tuples→lists), so the structural comparison must too
        self._spec = spec
        self._spec_json = json.loads(json.dumps(spec))
        self._descr = tuple((str(l.dtype), tuple(int(d) for d in l.shape))
                            for l in leaves)
        self.supported = bool(leaves) and all(
            d == "float32" for d, _ in self._descr)
        self._shapes = [tuple(int(d) for d in l.shape) for l in leaves]
        self._sizes = [int(l.size) for l in leaves]
        self._offsets = np.concatenate(
            ([0], np.cumsum(self._sizes))).astype(np.int64)
        self.n_elems = int(self._offsets[-1])
        self._device = device
        if not self.supported:
            return
        import jax
        import jax.numpy as jnp
        # the pre-pinned arena: reused across uploads (single consumer),
        # one device_put ships it whole
        self._flat = np.empty(self.n_elems, np.float32)
        self._ref = jax.device_put(np.zeros(self.n_elems, np.float32),
                                   device)

        def _screen(flat, ref):
            # fused finite + sumsq over the flat buffer: ONE reduction
            # pass replaces the per-leaf host all_finite + update_sumsq
            d = flat - ref
            return jnp.isfinite(flat).all(), jnp.sum(d * d)

        offsets, shapes = list(self._offsets[:-1]), self._shapes

        def _split(flat):
            # static slices: the arena's leaf layout is fixed, so this
            # traces once and returns device VIEWS into the staged flat
            # buffer — no host tree ever materializes
            return tuple(
                jax.lax.dynamic_slice(flat, (int(o),), (int(n),))
                .reshape(s)
                for o, n, s in zip(offsets, self._sizes, shapes))

        self._screen_fn = jax.jit(_screen)
        self._split_fn = jax.jit(_split)
        if perf is not None:
            # PR 9 compile ledger: the fused screen and the arena split
            # each key exactly ONE entry (the bench's 0-recompile gate)
            self._screen_fn = perf.instrument_jit(f"{name}_screen",
                                                  self._screen_fn)
            self._split_fn = perf.instrument_jit(f"{name}_arena",
                                                 self._split_fn)

    # -- round lifecycle -----------------------------------------------------
    def round_start(self, reference=None) -> None:
        """Stage the round's screen reference flat on the device (one
        transfer per round, the `_ref_cache` discipline).  ``None``
        keeps zeros — the ``kind="delta"`` norm measures the payload
        itself."""
        if not self.supported:
            return
        import jax
        if reference is None:
            self._ref = jax.device_put(
                np.zeros(self.n_elems, np.float32), self._device)
            return
        from fedml_tpu.comm.message import _flatten_arrays
        leaves, _ = _flatten_arrays(jax.tree.map(np.asarray, reference))
        flat = np.empty(self.n_elems, np.float32)
        for view, o, n in zip(leaves, self._offsets[:-1], self._sizes):
            np.copyto(flat[o:o + n],
                      np.asarray(view, np.float32).reshape(-1))
        self._ref = jax.device_put(flat, self._device)

    # -- the structural screen (header vs template, no tree walk) ------------
    def match_header(self, descr, spec) -> bool:
        """The zero-walk structural fingerprint: the frame header's leaf
        descriptors (dtype/shape in buffer order) AND its pytree spec
        must equal the template's.  Spec equality carries the leaf keys,
        so this is exactly as strong as
        `robust.admission.params_fingerprint` — a same-shape payload
        under different keys is still a reject."""
        try:
            # the wire writes ``arr.dtype.str`` ('<f4'); the template
            # stores the canonical name ('float32') — normalize to name
            got = tuple((np.dtype(d["dtype"]).name, tuple(d["shape"]))
                        for d in descr)
        except (TypeError, KeyError, ValueError):
            return False
        return got == self._descr and spec == self._spec_json

    # -- staging -------------------------------------------------------------
    def stage_message(self, msg, key) -> Optional[ArenaScreen]:
        """Stage one upload straight from its frame: the header's raw
        leaf descriptors index the frame's buffer views (no tree walk).
        Returns ``None`` when the message carries no raw frame (a
        pump-mode object message) — the caller falls back to
        `stage_tree` or the host path."""
        raw = msg.raw_payload(key) if hasattr(msg, "raw_payload") else None
        if raw is None or not self.supported:
            return None
        descr, spec, buffers = raw
        if not self.match_header(descr, spec):
            return ArenaScreen(structural_ok=False)
        views = []
        try:
            for d in descr:
                views.append(np.frombuffer(buffers[d["idx"]],
                                           dtype=np.float32))
        except (TypeError, ValueError, IndexError, KeyError):
            return ArenaScreen(structural_ok=False)
        if any(v.size != n for v, n in zip(views, self._sizes)):
            # torn frame: the header matched but a buffer's byte length
            # disagrees with its own descriptor — structural damage, not
            # a worker crash
            return ArenaScreen(structural_ok=False)
        return self._stage_views(views)

    def stage_tree(self, tree) -> Optional[ArenaScreen]:
        """Stage one upload from its decoded pytree (the leaves are the
        frame's zero-copy views — flattening touches references, never
        bytes).  Structure is screened against the template exactly like
        the raw-header path."""
        if not self.supported:
            return None
        from fedml_tpu.comm.message import _flatten_arrays
        try:
            leaves, spec = _flatten_arrays(tree)
        except Exception:  # noqa: BLE001 — garbage payload object
            return ArenaScreen(structural_ok=False)
        if json.loads(json.dumps(spec)) != self._spec_json:
            return ArenaScreen(structural_ok=False)
        if len(leaves) != len(self._descr):
            return ArenaScreen(structural_ok=False)
        views = []
        for leaf, (dtype, shape) in zip(leaves, self._descr):
            arr = np.asarray(leaf)
            if str(arr.dtype) != dtype \
                    or tuple(int(d) for d in arr.shape) != shape:
                return ArenaScreen(structural_ok=False)
            views.append(arr)
        return self._stage_views(views)

    def _stage_views(self, views: List[np.ndarray]) -> ArenaScreen:
        import jax
        flat = self._flat
        for v, o, n in zip(views, self._offsets[:-1], self._sizes):
            np.copyto(flat[o:o + n], v.reshape(-1))
        dev = jax.device_put(flat, self._device)  # ONE transfer per shard
        finite, sumsq = self._screen_fn(dev, self._ref)
        leaves = self._split_fn(dev)
        from fedml_tpu.comm.message import _unflatten_arrays
        tree = _unflatten_arrays(self._spec, list(leaves))
        sumsq = float(sumsq)
        return ArenaScreen(structural_ok=True, finite=bool(finite),
                           sumsq=sumsq,
                           norm=math.sqrt(max(sumsq, 0.0)), tree=tree)


class IngestPipeline:
    """Bounded per-shard ingest queues + one fold worker per shard.

    ``num_shards``: 1 for the replicated / secagg / async paths (a
    single FIFO worker IS the determinism proof — fold order == arrival
    order), S for the sharded wire.  ``depth`` bounds each queue
    (``--ingest_queue_depth``).  ``fault_feed(reason, detail)``: the
    resilient-transport seam — every overflow dead-letter feeds it so
    the degrade ledger attributes the drop as a NETWORK fault.

    ``arenas``: optional per-shard `IngestArena` list (attach via
    `attach_arenas`); ``arena_for(shard)`` hands the worker its shard's
    staging buffer.

    Worker exceptions are stored and re-raised from the next
    ``drain()`` / ``stop()`` — a fold that dies must fail the round
    loudly, never hang the barrier silently."""

    def __init__(self, *, num_shards: int = 1, depth: int = 64,
                 registry=None,
                 fault_feed: Optional[Callable[[str, str], None]] = None):
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        if depth < 1:
            raise ValueError(
                f"--ingest_queue_depth must be >= 1, got {depth}")
        self.num_shards = num_shards
        self.depth = depth
        reg = registry if registry is not None else telemetry.get_registry()
        self._gauges = IngestGauges(reg)
        # the dead-letter family the resilient transport owns, reason
        # "ingest_overflow": backpressure drops land in the SAME series
        # every dead-letter dashboard already watches
        self._c_dead = reg.counter("fedml_comm_dead_letter_total",
                                   reason=OVERFLOW_REASON)
        self._fault_feed = fault_feed
        self._arenas: Optional[List[Optional[IngestArena]]] = None
        self._queues = [queue.Queue(maxsize=depth)
                        for _ in range(num_shards)]
        self._unhandled: List[BaseException] = []
        self._processed = 0
        self._drained_at = 0
        self._lock = threading.Lock()
        # test seam: a paused pipeline enqueues but does not consume —
        # the kill-mid-queue recovery tests hold frames in flight with it
        self._resume_evt = threading.Event()
        self._resume_evt.set()
        self._stopped = False
        self._threads = [
            threading.Thread(target=self._worker, args=(q,),
                             name=f"ingest-fold-{s}", daemon=True)
            for s, q in enumerate(self._queues)]
        for t in self._threads:
            t.start()

    # -- arena wiring --------------------------------------------------------
    def attach_arenas(self, arenas: List[Optional[IngestArena]]) -> None:
        if len(arenas) != self.num_shards:
            raise ValueError(f"{len(arenas)} arenas for {self.num_shards} "
                             f"shard queues")
        self._arenas = arenas

    @property
    def has_arenas(self) -> bool:
        return self._arenas is not None

    def arena_for(self, shard: int) -> Optional[IngestArena]:
        if self._arenas is None:
            return None
        return self._arenas[shard]

    def round_start(self, references) -> None:
        """Per-round arena reference staging: ``references`` is a list
        of per-shard reference trees (or ``None`` entries for the
        zero/delta reference), one per shard queue."""
        if self._arenas is None:
            return
        for arena, ref in zip(self._arenas, references):
            if arena is not None:
                arena.round_start(ref)

    # -- the producer side ---------------------------------------------------
    def submit(self, shard: int, task: Callable[[], None],
               detail: str = "") -> bool:
        """Transport-path enqueue: non-blocking.  Returns False on
        overflow — the frame is dead-lettered (counter + fault feed,
        NETWORK attribution) and the caller must NOT strike trust."""
        self._check_shard(shard)
        self._raise_unhandled()
        try:
            self._queues[shard].put_nowait(task)
        except queue.Full:
            self._gauges.note_overflow(shard)
            self._c_dead.inc()
            log.warning("ingest queue %d full (depth %d): dead-lettering "
                        "%s as a network fault", shard, self.depth,
                        detail or "frame")
            if self._fault_feed is not None:
                self._fault_feed(OVERFLOW_REASON, detail)
            return False
        self._note_enqueued(shard)
        return True

    def submit_wait(self, shard: int, task: Callable[[], None]) -> None:
        """Producer-blocking enqueue (the cross-device wave path): the
        producer is the local wave engine, so backpressure means WAIT —
        a wave is never a droppable network frame."""
        self._check_shard(shard)
        self._raise_unhandled()
        self._queues[shard].put(task)
        self._note_enqueued(shard)

    def _note_enqueued(self, shard: int) -> None:
        self._gauges.note_enqueued(self._queues[shard].qsize())

    def _check_shard(self, shard: int) -> None:
        if not 0 <= shard < self.num_shards:
            raise ValueError(f"shard {shard} outside the pipeline's "
                             f"{self.num_shards} queues")

    # -- the consumer side ---------------------------------------------------
    def _worker(self, q: "queue.Queue") -> None:
        while True:
            task = q.get()
            if task is _STOP:
                q.task_done()
                return
            self._resume_evt.wait()
            try:
                task()
            except BaseException as e:  # noqa: BLE001 — must surface
                log.exception("ingest fold worker died processing a task")
                with self._lock:
                    self._unhandled.append(e)
            finally:
                with self._lock:
                    self._processed += 1
                self._gauges.note_depth(q.qsize())
                q.task_done()

    # -- barrier / lifecycle -------------------------------------------------
    def drain(self) -> int:
        """Block until every enqueued task has been processed; returns
        how many tasks completed since the previous drain (the pump
        idle-hook progress signal).  Re-raises the first worker
        exception — a dead fold must fail the caller, not wedge the
        barrier."""
        for q in self._queues:
            q.join()
        self._raise_unhandled()
        with self._lock:
            progress = self._processed - self._drained_at
            self._drained_at = self._processed
        return progress

    def pause(self) -> None:
        """Test seam: workers finish their CURRENT task and then hold —
        enqueued frames stay queued (the kill-mid-queue fixture)."""
        self._resume_evt.clear()

    def resume(self) -> None:
        self._resume_evt.set()

    def _raise_unhandled(self) -> None:
        with self._lock:
            if self._unhandled:
                exc = self._unhandled[0]
                self._unhandled = []
                raise RuntimeError(
                    "ingest fold worker died; the round cannot complete"
                ) from exc

    def stop(self) -> None:
        """Idempotent shutdown: stop sentinels, join the workers, then
        surface any worker exception.  Callable from a fold worker
        itself (a barrier close that ends the federation runs there) —
        the calling thread is never joined."""
        if self._stopped:
            return
        self._stopped = True
        self._resume_evt.set()
        for q in self._queues:
            q.put(_STOP)
        me = threading.current_thread()
        for t in self._threads:
            if t is not me:
                t.join(timeout=10.0)
        self._raise_unhandled()
