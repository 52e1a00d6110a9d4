"""TPU-native serving: the train → aggregate → checkpoint → **serve** leg.

The reference FedML stack (and PRs 0-2 here) ends at the aggregated
checkpoint — there is no path from a federation round to an inference
request.  This package closes the loop, stdlib-only (plus jax), in three
layers plus a bench harness:

    fedml_tpu.serve.registry  versioned model registry: atomic hot-swap of
                              the live (params, apply_fn, version) triple,
                              pin/rollback, background checkpoint watcher
                              (serve-while-train against RoundCheckpointer)
    fedml_tpu.serve.batcher   dynamic micro-batching queue: size/deadline
                              flush triggers, power-of-two shape buckets
                              (one jit compile per bucket — the FedJAX
                              static-shapes lesson, arXiv:2108.02117),
                              deadline-based load shedding, drain-on-stop
    fedml_tpu.serve.server    ThreadingHTTPServer frontend (/predict,
                              /healthz, /version, /metrics) with admission
                              control and per-request deadline propagation
    fedml_tpu.serve.pool      multi-worker frontend (ISSUE 15): N
                              SO_REUSEPORT accept loops × N micro-batchers
                              over ONE shared registry, worker-labeled
                              telemetry, pool-wide health payloads
    fedml_tpu.serve.decode    continuous-batching decode scheduler for
                              autoregressive models: one compiled step
                              over fixed [slots], per-step slot admission,
                              swap-barrier version consistency
    fedml_tpu.serve.release   train-to-serve release gate (ISSUE 16):
                              every finalized global enters as a CANARY;
                              promotion gated on shadow-traffic
                              divergence, health-observatory alarms, and
                              held-out eval regression — fail rolls back
                              (the live slot never moved) with cooldown/
                              backoff, all crash-consistent

Everything is instrumented through the PR 2 telemetry registry under
``fedml_serve_*`` (see the README metric table) and designed to survive
chaos: a mid-load hot swap must never produce a torn read (the whole
triple swaps as one immutable snapshot), and a checkpoint directory GC'd
between list and load is tolerated, not fatal.
"""

from fedml_tpu.serve.batcher import (MicroBatcher, ShedError, TierGate,
                                     TIERS)
from fedml_tpu.serve.decode import DecodeResult, DecodeScheduler
from fedml_tpu.serve.pool import ServeWorkerPool
from fedml_tpu.serve.registry import ModelRegistry, ServedModel
from fedml_tpu.serve.release import ReleaseController, ShadowSampler
from fedml_tpu.serve.server import ServeFrontend

__all__ = ["MicroBatcher", "ShedError", "TierGate", "TIERS",
           "DecodeResult", "DecodeScheduler", "ServeWorkerPool",
           "ModelRegistry", "ServedModel", "ServeFrontend",
           "ReleaseController", "ShadowSampler"]
