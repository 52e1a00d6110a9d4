"""The benchmark's own clock and tap on the program's round loop.

The program writes ``perf.jsonl`` with its own timestamps; an end-to-end
metric may not be read from the program, so the harness wraps the one
method a traffic file names as the round's entry (``round_hook.target``,
``module:Class.method`` or ``module:function``) and stamps ITS clock each
time the program enters it.  Consecutive stamps are whole round cycles:
everything the process does between two entries (training, fold, server
step, CRC, ledger line, sampling of the next cohort) lies between them.

The same wrap hands the comparison what the timed path produced: the global
model going into the first round (g0), coming out of it (g1) and coming out
of round ``keep`` (gK), the globals ``reference/fedavg.compare`` reads, and
the cohort ids of every round (to count the samples the window consumed).
The globals are copied to host memory as they are taken, which waits for
that round's last program; ``keep`` rounds are set-up and lie
before the window opens (``keep <= first``), so nothing of the harness's
stays on the chip while ``MemoryWatch`` reads it and the window's rounds are
entered as if no global had been kept.  While the window
is open a thread reads the chips' memory counters (``MemoryWatch``), so
that a cell's memory is what its timed traffic holds and not what set-up
once took.  With ``trace_dir`` the rounds that follow the window are
traced: JAX's profiler starts once the window's closing stamp is taken and
each traced round cycle lies in an annotation ``bench_round``, so the
traced interval is whole cycles on the trace's own clock and holds neither
the profiler's start nor its stop.

Nothing here names a cell, a model or an algorithm: the names come from
the traffic file.
"""

from __future__ import annotations

import contextlib
import importlib
import threading
import time
from typing import Any, Callable, Dict, List, Optional

ROUND_SPAN = "bench_round"   # the annotation around a traced round cycle


def resolve(target: str):
    """``pkg.mod:Class.method`` -> (owner object, attribute name)."""
    mod_name, _, path = target.partition(":")
    owner = importlib.import_module(mod_name)
    parts = path.split(".")
    for p in parts[:-1]:
        owner = getattr(owner, p)
    if not hasattr(owner, parts[-1]):
        raise AttributeError(f"{target}: {owner!r} has no {parts[-1]!r}")
    return owner, parts[-1]


@contextlib.contextmanager
def patched(target: str, make_wrapper: Callable[[Callable], Callable]):
    owner, name = resolve(target)
    original = getattr(owner, name)
    setattr(owner, name, make_wrapper(original))
    try:
        yield
    finally:
        setattr(owner, name, original)


class MemoryWatch:
    """The chips' memory while the window is open.

    ``memory_stats()`` keeps two peaks for the life of the process, and a
    process's peak never falls: whatever set-up once held (the round-0
    evaluation over the whole population) would stand for the cell.  So a
    thread reads the CURRENT counters every ``every`` seconds between the
    window's open and close: ``bytes_in_use`` (buffers the process holds)
    and ``bytes_reserved`` (the scratch region, as large as the largest
    program loaded so far needs; the two do not overlap), and keeps the
    largest sum a chip showed.  ``bytes_reserved`` does not fall while its
    program stays loaded, so the thread runs from the call's first round
    and ``steps`` keeps every change of the first chip's (seconds since
    the first round's entry, bytes): where set-up loaded a program with
    more scratch than the window's own, the record shows it."""

    KEYS = ("bytes_in_use", "bytes_reserved")

    def __init__(self, chips: int, every: float = 0.05):
        self.chips = chips
        self.every = every
        self.samples = 0
        self.peak = [dict.fromkeys(self.KEYS + ("sum",), 0)
                     for _ in range(chips)]
        self.at_open = self.at_close = None
        self.steps: List[list] = []
        self._t0 = None
        self._stop = threading.Event()
        self._thread = None

    def read(self) -> List[dict]:
        import jax
        out = []
        for d in jax.devices()[:self.chips]:
            stats = d.memory_stats() or {}
            out.append({k: int(stats.get(k, 0)) for k in self.KEYS})
        return out

    def _take(self) -> List[dict]:
        now = self.read()
        self.samples += 1
        reserved = now[0]["bytes_reserved"]
        if (not self.steps or self.steps[-1][1] != reserved) \
                and len(self.steps) < 32:
            self.steps.append([time.perf_counter() - self._t0, reserved])
        for peak, row in zip(self.peak, now):
            for k in self.KEYS:
                peak[k] = max(peak[k], row[k])
            peak["sum"] = max(peak["sum"], sum(row.values()))
        return now

    def _loop(self):
        while not self._stop.wait(self.every):
            self._take()

    def start(self):
        """From here on ``steps`` is kept (the call's first round)."""
        if self._thread is not None:
            return
        self._t0 = time.perf_counter()
        self._take()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="bench-memory-watch")
        self._thread.start()

    def open(self):
        """The window opens: the peaks are the window's from here on."""
        if self._thread is None:
            self.start()
        self.samples = 0
        for peak in self.peak:
            for k in peak:
                peak[k] = 0
        self.at_open = self._take()

    def stop(self):
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join()
        self._thread = None
        self.at_close = self._take()

    def peak_bytes(self) -> int:
        """The most a chip held at one reading inside the window."""
        return max(p["sum"] for p in self.peak)


class RoundProbe:
    """Stamps, states and cohorts of one ``main()`` call.

    ``first`` is the index (0-based, among this call's rounds) of the
    first round of the window; rounds ``first .. first+n-1`` are the
    window, and the stamp that closes it is the entry of round
    ``first+n``.  With ``trace_dir`` the ``n_traced`` rounds from there on
    are traced and the entry of round ``first+n+n_traced`` ends the trace;
    the call is given one round more than all of these.
    """

    def __init__(self, spec: dict, first: int, n_window: int, keep: int,
                 trace_dir: Optional[str] = None, n_traced: int = 0,
                 compile_snapshot: Optional[Callable[[], dict]] = None,
                 memory: Optional[MemoryWatch] = None):
        if keep > first:
            raise ValueError(f"keep={keep} rounds are set-up's and end "
                             f"before the window opens at round {first}")
        self.spec = spec
        self.first = first
        self.n_window = n_window
        self.keep = keep
        self.trace_dir = trace_dir
        self.n_traced = n_traced if trace_dir is not None else 0
        self.compile_snapshot = compile_snapshot
        self.memory = memory
        self.stamps: List[float] = []       # time.time() at each entry
        self.stamps_mono: List[float] = []  # perf_counter at each entry
        self.cohorts: List[Any] = []
        self.state_in = None
        self.states_out: Dict[int, Any] = {}   # g1 and gK, by round index
        self.compiles_at: Dict[str, dict] = {}
        self.tracing = False
        self._span = None
        self.closed = self.closed_mono = None  # the window's closing stamp

    @property
    def rounds_needed(self) -> int:
        return self.first + self.n_window + self.n_traced + 1

    # -- the wrapper ---------------------------------------------------------
    def wrap(self, original):
        spec = self
        state_arg = self.spec.get("state_arg")
        cohort_arg = self.spec.get("cohort_arg")
        state_out = self.spec.get("state_out")

        def hooked(*args, **kwargs):
            k = len(spec.stamps)
            end = spec.first + spec.n_window
            if k == end:
                spec._close_window()
            if spec.tracing:
                spec._end_traced_round(last=(k == end + spec.n_traced))
            spec.stamps_mono.append(time.perf_counter())
            spec.stamps.append(time.time())
            if k == spec.first:
                spec._open_window()
            if k == end and spec.n_traced:
                spec._start_trace()
            if spec.tracing:
                spec._begin_traced_round()
            if cohort_arg is not None:
                spec.cohorts.append(args[cohort_arg])
            if k == 0 and spec.memory is not None:
                spec.memory.start()
            if k == 0 and spec.keep and state_arg is not None:
                spec.state_in = _host_copy(args[state_arg])
            out = original(*args, **kwargs)
            if spec.keep and k in (0, spec.keep - 1) \
                    and state_out is not None:
                spec.states_out[k + 1] = _host_copy(out[state_out])
            return out

        return hooked

    def _open_window(self):
        if self.compile_snapshot is not None:
            self.compiles_at["open"] = self.compile_snapshot()
        if self.memory is not None:
            self.memory.open()

    def _close_window(self):
        # the stamp that closes the window comes first: what follows is
        # not the window's
        self.closed_mono = time.perf_counter()
        self.closed = time.time()
        if self.memory is not None:
            self.memory.stop()
        if self.compile_snapshot is not None:
            self.compiles_at["close"] = self.compile_snapshot()

    def _start_trace(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.tracing = True

    def _begin_traced_round(self):
        import jax
        self._span = jax.profiler.TraceAnnotation(ROUND_SPAN)
        self._span.__enter__()

    def _end_traced_round(self, last: bool):
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        if last:
            import jax
            jax.profiler.stop_trace()
            self.tracing = False

    # -- what the window was -------------------------------------------------
    def window(self) -> dict:
        """Entry stamps of the window's rounds plus the closing stamp, and
        the cohorts of the window's and of the traced rounds."""
        a, b = self.first, self.first + self.n_window
        if len(self.stamps) < self.rounds_needed or self.closed is None:
            raise RuntimeError(
                f"the program entered {len(self.stamps)} rounds; the "
                f"call needs {self.rounds_needed}")
        edges = self.stamps_mono[a:b] + [self.closed_mono]
        return {"edges_mono": edges,
                "start_wall": self.stamps[a], "end_wall": self.closed,
                "cohorts": self.cohorts[a:b],
                "traced_cohorts": self.cohorts[b:b + self.n_traced]}


def _host_copy(tree):
    """A copy in host memory, owned by the harness: the program can
    neither donate it away nor (on a backend whose arrays live in host
    memory) write through it.  Waits for the tree to be computed."""
    import jax
    import numpy as np
    return jax.tree.map(np.array, tree)


def span_wrapper(name: str):
    """Wrap a program function in a profiler annotation (traced runs
    only), so that idle gaps on the device can be named by what the host
    was doing."""
    def make(original):
        import jax

        def spanned(*args, **kwargs):
            with jax.profiler.TraceAnnotation(name):
                return original(*args, **kwargs)
        return spanned
    return make
