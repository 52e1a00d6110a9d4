"""Experiments/CLI layer tests.

The reference's equivalent coverage is its CI shell scripts
(``CI-script-fedavg.sh:33-38``: smoke-run every dataset×model combo from the
shell, then assert on the wandb summary).  Here the CLI is a function
(`fedml_tpu.experiments.main.main`), so the smoke runs are in-process and
the "wandb summary" assertions read the run_dir artifacts.
"""

import json
import os

import numpy as np
import pytest

from fedml_tpu.experiments.config import build_parser, ExperimentConfig
from fedml_tpu.experiments.main import RUNNERS, main
from fedml_tpu.utils.metrics import MetricsSink

# every behavioral flag of the reference argparse surface
# (main_fedavg.py:46-112) that carries over by name
REFERENCE_FLAGS = [
    "model", "dataset", "data_dir", "partition_method", "partition_alpha",
    "client_num_in_total", "client_num_per_round", "batch_size",
    "client_optimizer", "lr", "wd", "epochs", "comm_round",
    "frequency_of_the_test", "ci",
]

_BASE = ["--client_num_in_total", "8", "--client_num_per_round", "4",
         "--comm_round", "2", "--frequency_of_the_test", "1",
         "--batch_size", "4", "--log_stdout", "false"]


def test_parser_reference_flag_parity():
    parser = build_parser()
    opts = {a.dest for a in parser._actions}
    missing = [f for f in REFERENCE_FLAGS if f not in opts]
    assert not missing, f"CLI lost reference flags: {missing}"


def test_all_algorithms_registered():
    expected = {"fedavg", "fedprox", "fedopt", "fednova", "fedavg_robust",
                "hierarchical", "centralized", "decentralized",
                "turboaggregate", "fednas", "fedgkt", "fedgan", "asdgan",
                "fedseg", "split_nn", "vfl", "cross_silo"}
    assert expected <= set(RUNNERS), sorted(expected - set(RUNNERS))


def test_cli_fedavg_end_to_end(tmp_path):
    run_dir = str(tmp_path / "run")
    summary = main(["--algo", "fedavg", "--model", "lr",
                    "--dataset", "mnist", "--run_dir", run_dir] + _BASE)
    assert "train_acc" in summary and "test_acc" in summary
    # wandb-equivalent artifacts (CI-script-fedavg.sh:43-48 reads the
    # wandb summary; our CI reads summary.json)
    with open(os.path.join(run_dir, "summary.json")) as f:
        persisted = json.load(f)
    assert persisted["final"]["train_acc"] == summary["train_acc"]
    events = [json.loads(l) for l in
              open(os.path.join(run_dir, "metrics.jsonl"))]
    rounds = [e["step"] for e in events if "round" in e and "step" in e]
    assert rounds == [0, 1]


def test_cli_mesh_equals_single_chip(devices):
    """The CLI's --mesh_clients path must reproduce the single-chip run
    bit-comparably (same cohort rng convention, psum vs vmap aggregation)."""
    argv = ["--algo", "fedavg", "--model", "lr", "--dataset", "mnist",
            "--client_num_in_total", "16", "--client_num_per_round", "8"] \
        + _BASE[4:]
    single = main(argv)
    sharded = main(argv + ["--mesh_clients", "8"])
    np.testing.assert_allclose(single["train_acc"], sharded["train_acc"],
                               rtol=1e-6)
    np.testing.assert_allclose(single["train_loss"], sharded["train_loss"],
                               rtol=1e-5)


def test_cli_ci_mode_restricts_eval(tmp_path):
    run_dir = str(tmp_path / "ci")
    summary = main(["--algo", "fedavg", "--model", "lr", "--dataset",
                    "mnist", "--comm_round", "6", "--ci", "1",
                    "--run_dir", run_dir] + _BASE[:4] + _BASE[8:])
    assert summary["round"] == 5
    events = [json.loads(l) for l in
              open(os.path.join(run_dir, "metrics.jsonl"))]
    evaluated = [e["round"] for e in events if "train_acc" in e]
    assert evaluated == [0, 5]  # round 0 + final only


@pytest.mark.parametrize("algo", ["fedopt", "centralized", "vfl"])
def test_cli_fast_algos(algo):
    summary = main(["--algo", algo, "--model", "lr", "--dataset", "mnist"]
                   + _BASE)
    assert summary


# big-model compiles dominate these CLI combos on CPU -> slow tier
_HEAVY_ALGOS = {"fednas", "fedgkt", "fedseg", "asdgan", "fedgan"}


@pytest.mark.parametrize(
    "algo", [pytest.param(a, marks=pytest.mark.slow)
             if a in _HEAVY_ALGOS else a for a in sorted(RUNNERS)])
def test_cli_every_algorithm(algo, tmp_path):
    """Every algorithm × the CLI runs end-to-end on hermetic data (the
    reference CI's per-combo smoke strategy)."""
    special = {
        "fednas": ["--dataset", "femnist", "--fednas_layers", "2",
                   "--fednas_channels", "4"],
        "fedgkt": ["--dataset", "femnist"],
        "fedgan": ["--dataset", "femnist"],
        "asdgan": ["--dataset", "femnist"],
        "fedseg": ["--dataset", "femnist"],
        "hierarchical": ["--group_num", "2", "--group_comm_round", "1"],
        "decentralized_online": ["--iteration_number", "30", "--lr", "0.3",
                                 "--wd", "0"],
        "turboaggregate": ["--group_num", "2"],
    }
    argv = (["--algo", algo, "--model", "lr", "--dataset", "mnist"]
            + _BASE + special.get(algo, [])
            + ["--run_dir", str(tmp_path / algo)])
    summary = main(argv)
    assert isinstance(summary, dict) and summary
    assert os.path.exists(tmp_path / algo / "summary.json")


def test_cli_cross_silo_matches_fedavg(tmp_path):
    """The actor-choreography path (local hub, wire codec on) must land at
    the same aggregate as the in-jit fedavg cohort for one full-batch
    round: same seeded sampling, same local SGD, same weighted mean."""
    argv = ["--model", "lr", "--dataset", "mnist",
            "--client_num_in_total", "4", "--client_num_per_round", "4",
            "--comm_round", "1", "--frequency_of_the_test", "1",
            "--batch_size", "64", "--epochs", "1", "--log_stdout", "false"]
    silo = main(["--algo", "cross_silo"] + argv)
    fed = main(["--algo", "fedavg"] + argv)
    np.testing.assert_allclose(silo["train_acc"], fed["train_acc"], rtol=1e-6)
    np.testing.assert_allclose(silo["train_loss"], fed["train_loss"],
                               rtol=1e-5)


@pytest.mark.slow
def test_cli_cross_silo_pipeline_stages(tmp_path):
    """--mesh_stages: cross-silo federation where every silo trains its
    transformer through the 2-stage GPipe pipeline (CPU devices stand in
    for the stage chips).  Must run end-to-end AND compose with
    --moe_experts (the ep x pp balance-loss path)."""
    argv = ["--algo", "cross_silo", "--model", "transformer",
            "--dataset", "shakespeare", "--mesh_stages", "2",
            "--client_num_in_total", "4", "--client_num_per_round", "2",
            "--comm_round", "1", "--frequency_of_the_test", "1",
            "--batch_size", "4", "--epochs", "1", "--log_stdout", "false"]
    out = main(argv)
    assert np.isfinite(out["train_loss"])
    import jax as _jax
    if hasattr(_jax, "shard_map"):
        out_moe = main(argv + ["--moe_experts", "2"])
        assert np.isfinite(out_moe["train_loss"])
    else:
        # legacy toolchain: the MoE schedule refuses loudly by contract
        with pytest.raises(RuntimeError, match="jax.shard_map"):
            main(argv + ["--moe_experts", "2"])


def test_cli_mesh_stages_rejected_outside_cross_silo():
    with pytest.raises(ValueError, match="mesh_stages"):
        main(["--algo", "fedavg", "--model", "transformer", "--dataset",
              "shakespeare", "--mesh_stages", "2"] + _BASE)


@pytest.mark.slow
def test_cli_cross_silo_grpc_loopback(tmp_path):
    """True multi-process federation: server + 2 silo processes over gRPC
    on 127.0.0.1 (the reference's localhost-MPI strategy, SURVEY.md §4.3,
    with grpc_ipconfig.csv-style peers)."""
    import subprocess
    import sys
    base = ["--algo", "cross_silo", "--silo_backend", "grpc",
            "--platform", "cpu", "--model", "lr", "--dataset", "mnist",
            "--client_num_in_total", "8", "--client_num_per_round", "2",
            "--comm_round", "2", "--frequency_of_the_test", "1",
            "--batch_size", "4", "--base_port", "52310",
            "--log_stdout", "false"]
    base += ["--silo_idle_timeout_s", "120"]  # no leaked silos on failure
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    silos = [subprocess.Popen(
        [sys.executable, "-m", "fedml_tpu", "--node_id", str(i)] + base,
        cwd=repo, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT) for i in (1, 2)]
    try:
        # no sleep: the server's INIT broadcast uses wait_for_ready, so it
        # blocks until each silo's grpc server binds
        server = subprocess.run(
            [sys.executable, "-m", "fedml_tpu", "--node_id", "0"] + base,
            cwd=repo, env=env, capture_output=True, text=True, timeout=240)
        for p in silos:
            p.wait(timeout=60)
    finally:
        for p in silos:
            if p.poll() is None:
                p.kill()
    assert server.returncode == 0, server.stdout + server.stderr
    assert '"train_acc"' in server.stdout


def test_completion_signal_file(tmp_path):
    """--completion_signal writes the final summary line (the reference's
    sweep-orchestration named-pipe contract, fedavg/utils.py:19-27)."""
    sig = tmp_path / "done"
    summary = main(["--algo", "fedavg", "--model", "lr", "--dataset",
                    "mnist", "--completion_signal", str(sig)] + _BASE)
    line = json.loads(sig.read_text())
    assert line["algo"] == "fedavg"
    assert line["train_acc"] == summary["train_acc"]


def test_metrics_sink(tmp_path):
    with MetricsSink(str(tmp_path)) as sink:
        sink.log({"acc": 0.5}, step=0)
        sink.log({"acc": np.float32(0.75), "loss": 1.0}, step=1)
    assert sink.summary["acc"] == 0.75
    with open(tmp_path / "summary.json") as f:
        assert json.load(f)["acc"] == 0.75
    lines = open(tmp_path / "metrics.jsonl").read().splitlines()
    assert len(lines) == 2 and json.loads(lines[0])["acc"] == 0.5


def test_config_dataclass_roundtrip():
    cfg = ExperimentConfig(algo="fedprox", mu=0.5)
    assert cfg.mu == 0.5 and cfg.algo == "fedprox"


@pytest.mark.slow
def test_multiprocess_distributed_matches_single(tmp_path):
    """Two OS processes x 4 virtual CPU devices each, wired by
    jax.distributed.initialize, must reproduce the single-process 8-device
    run bit-comparably (the mpirun -np N replacement, LAUNCH.md)."""
    import subprocess
    import sys

    driver = tmp_path / "mp_driver.py"
    driver.write_text(
        "import sys, json\n"
        "sys.path.insert(0, %r)\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "pid = int(sys.argv[1])\n"
        "from fedml_tpu.parallel.mesh import init_distributed\n"
        "init_distributed('127.0.0.1:29891', 2, pid)\n"
        "from fedml_tpu.experiments.main import main\n"
        "s = main(['--algo', 'fedavg', '--model', 'lr', '--dataset',"
        " 'mnist', '--client_num_in_total', '16',"
        " '--client_num_per_round', '8', '--comm_round', '2',"
        " '--batch_size', '4', '--frequency_of_the_test', '1',"
        " '--mesh_clients', '8', '--log_stdout', 'false'])\n"
        "print('RESULT', json.dumps(s))\n" % os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env.pop("JAX_PLATFORMS", None)
    procs = [subprocess.Popen(
        [sys.executable, "-u", str(driver), str(i)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env)
        for i in range(2)]
    outs = [p.communicate(timeout=300)[0] for p in procs]
    assert all(p.returncode == 0 for p in procs), outs
    results = []
    for out in outs:
        line = [l for l in out.splitlines() if l.startswith("RESULT ")]
        assert line, out
        results.append(json.loads(line[-1][len("RESULT "):]))
    assert results[0]["train_acc"] == results[1]["train_acc"]

    # single-process 8-virtual-device reference (this pytest process)
    single = main(["--algo", "fedavg", "--model", "lr", "--dataset",
                   "mnist", "--client_num_in_total", "16",
                   "--client_num_per_round", "8", "--comm_round", "2",
                   "--batch_size", "4", "--frequency_of_the_test", "1",
                   "--mesh_clients", "8", "--log_stdout", "false"])
    np.testing.assert_allclose(results[0]["train_acc"],
                               single["train_acc"], rtol=1e-6)
    np.testing.assert_allclose(results[0]["train_loss"],
                               single["train_loss"], rtol=1e-5)


@pytest.mark.parametrize("dataset", [
    "shakespeare",
    pytest.param("stackoverflow_nwp", marks=pytest.mark.slow),
    "stackoverflow_lr", "fed_cifar100", "cinic10"])
def test_cli_dataset_axis(dataset, tmp_path):
    """The dataset axis end-to-end through the CLI (this path held
    a latent logits-shape bug precisely because only --dataset mnist was
    smoke-tested)."""
    argv = ["--algo", "fedavg", "--dataset", dataset,
            "--client_num_in_total", "4", "--client_num_per_round", "2",
            "--comm_round", "1", "--batch_size", "4", "--epochs", "1",
            "--frequency_of_the_test", "1", "--log_stdout", "false",
            "--run_dir", str(tmp_path / dataset)]
    summary = main(argv)
    assert np.isfinite(summary.get("train_loss", np.inf))


def test_cli_profiler_trace(tmp_path):
    """--profile_dir captures a jax profiler trace alongside the run
    (SURVEY §5.1 observability; the reference has no profiling at all)."""
    prof = tmp_path / "trace"
    main(["--algo", "fedavg", "--model", "lr", "--dataset", "mnist",
          "--profile_dir", str(prof)] + _BASE)
    captured = list(prof.rglob("*.pb")) + list(prof.rglob("*.json.gz"))
    assert captured, f"no trace artifacts under {prof}"


def test_flagship_partial_sink_checkpoints_curve(tmp_path):
    """scripts/flagship_accuracy.py's PartialSink must leave the measured
    curve on disk after EVERY eval — a run killed mid-flagship still
    yields an artifact."""
    import importlib.util
    import json as _json
    import os as _os
    spec = importlib.util.spec_from_file_location(
        "flagship_accuracy",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), "scripts", "flagship_accuracy.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    from fedml_tpu.algorithms.fedavg import FedAvg, FedAvgConfig
    from fedml_tpu.data.synthetic import synthetic_federated_dataset
    from fedml_tpu.models import LogisticRegression
    from fedml_tpu.trainer.workload import ClassificationWorkload

    path = str(tmp_path / "CURVE.json.partial")
    sink = mod.PartialSink(path, {"rounds": 4})
    data = synthetic_federated_dataset(num_clients=6, samples_per_client=12,
                                       sample_shape=(5,), class_num=3,
                                       batch_size=4)
    wl = ClassificationWorkload(LogisticRegression(5, 3), num_classes=3,
                                grad_clip_norm=None)
    cfg = FedAvgConfig(comm_round=4, client_num_per_round=3, epochs=1,
                       batch_size=4, lr=0.1, frequency_of_the_test=2, seed=0)
    FedAvg(wl, data, cfg, sink=sink).run()
    part = _json.loads(open(path).read())
    assert part["partial"] is True
    curve = part["federated_curve_so_far"]
    # evals at rounds 0, 2, 3 (every 2 + final)
    assert [c["round"] for c in curve] == [0, 2, 3]
    assert all(c["train_acc"] is not None for c in curve)


@pytest.mark.parametrize("algo,extra", [
    ("scaffold", []),
    ("feddyn", ["--feddyn_alpha", "0.05"]),
    ("ditto", ["--ditto_lambda", "0.1"]),
    ("fedac", ["--fedac_mu", "0.1"]),
    ("dp_fedavg", ["--dp_clip", "0.5", "--dp_noise_multiplier", "1.0"]),
])
def test_cli_stateful_mesh_equals_single_chip(devices, algo, extra):
    """--mesh_clients on the stateful/coupled algorithms (whose mesh paths
    are the shared sharded round bodies) must reproduce the single-chip
    CLI run to float tolerance — covering the experiments/main.py wiring,
    not just the library API."""
    argv = ["--algo", algo, "--model", "lr", "--dataset", "mnist"] \
        + _BASE + extra
    single = main(argv)
    sharded = main(argv + ["--mesh_clients", "4"])
    np.testing.assert_allclose(single["train_loss"], sharded["train_loss"],
                               rtol=1e-5)
    np.testing.assert_allclose(single["train_acc"], sharded["train_acc"],
                               rtol=1e-5)


def test_top_level_api_lazy_exports():
    """`import fedml_tpu` must stay cheap (no jax import at package
    import time — platform selection must still be possible afterwards),
    while the curated names resolve lazily and point at the real
    objects."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # fresh interpreter: importing the package must not pull in jax
    code = (f"import sys; sys.path.insert(0, {repo!r}); "
            "import fedml_tpu; "
            "assert 'jax' not in sys.modules, 'package import pulled jax'; "
            "print('lazy-ok')")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True)
    assert "lazy-ok" in proc.stdout, proc.stderr

    import fedml_tpu
    from fedml_tpu.algorithms import FedAvg
    assert fedml_tpu.FedAvg is FedAvg
    assert "FedAvg" in dir(fedml_tpu)
    with pytest.raises(AttributeError):
        fedml_tpu.not_a_symbol
