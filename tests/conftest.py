"""Test harness: run everything on a virtual 8-device CPU mesh.

This replaces the reference's "multi-node without a cluster" strategy of
launching N+1 MPI processes on localhost
(run_fedavg_distributed_pytorch.sh:19) — here the N "processes" are N virtual
XLA devices inside one pytest process.  Plain env vars stick (nothing
imports jax before this file), so the harness is: JAX_PLATFORMS=cpu, 8
forced host devices, and the same platform pinned in jax's config.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import json  # noqa: E402

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture
def rng():
    return np.random.RandomState(0)


def identity_lm_data(vocab=12, clients=4, samples=16, seq=8, batch=8,
                     seed=13):
    """Deterministic next-token (y_t = x_t) federated LM dataset — the
    shared learning-proof task for the NLP families (RNN + transformer):
    any sequence model must drive token accuracy to ~1.  Tokens start at 2
    so labels never collide with NWPWorkload's pad_id=0 mask."""
    from fedml_tpu.data.stacking import FederatedData, stack_client_data
    rs = np.random.RandomState(seed)
    xs = [rs.randint(2, vocab, (samples, seq)).astype(np.int32)
          for _ in range(clients)]
    ys = [x.copy() for x in xs]
    train = stack_client_data(xs, ys, batch_size=batch)
    return FederatedData(client_num=clients, class_num=vocab, train=train,
                         test=train)


def trace_events(path):
    """A ``trace.json`` and its complete (``X``) events."""
    with open(path) as f:
        doc = json.load(f)
    return doc, [e for e in doc["traceEvents"] if e["ph"] == "X"]


@pytest.fixture(scope="module", params=["inline", "ingest_pipeline"])
def cli_run(request, tmp_path_factory):
    """One short ``--algo cross_device --perf`` run through the CLI: its
    ``trace.json`` and ``perf.jsonl``.  ``ingest_pipeline`` is the run with
    every timing site live (`fold.drain` / `barrier_wait` exist only
    there, `health` only under ``--health``)."""
    from fedml_tpu.experiments.main import main
    run_dir = str(tmp_path_factory.mktemp(request.param))
    argv = ["--algo", "cross_device", "--model", "lr", "--dataset", "mnist",
            "--client_num_in_total", "12", "--client_num_per_round", "10",
            "--wave_size", "4", "--comm_round", "2", "--batch_size", "4",
            "--health", "true", "--log_stdout", "false", "--perf", "true",
            "--run_dir", run_dir]
    if request.param == "ingest_pipeline":
        argv += ["--ingest_pipeline", "true"]
    main(argv)
    doc, events = trace_events(os.path.join(run_dir, "trace.json"))
    with open(os.path.join(run_dir, "perf.jsonl")) as f:
        ledger = [json.loads(line) for line in f]
    return {"doc": doc, "events": events, "ledger": ledger,
            "pipelined": request.param == "ingest_pipeline"}
