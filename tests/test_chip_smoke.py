"""The on-chip contracts, pinned on the CPU (all fast, nothing compiles a
model): where the compile cache lives, that chip_smoke.py refuses a host
without a TPU, the one compiled-or-interpreted decision for the Pallas
kernels, and that nothing on the main path hides the device it ran on.
"""

import importlib
import inspect
import json
import pathlib

import jax
import numpy as np
import pytest

cli = importlib.import_module("fedml_tpu.experiments.main")

REPO = pathlib.Path(__file__).resolve().parent.parent


# -- compile cache -----------------------------------------------------------

class _ConfigSpy:
    def __init__(self, monkeypatch, backend):
        self.updates = {}
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: self.updates.__setitem__(k, v))


def test_cache_env_set_code_sets_no_directory(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    spy = _ConfigSpy(monkeypatch, "tpu")
    assert cli.compile_cache_dir() is None
    cli.enable_compile_cache()
    assert "jax_compilation_cache_dir" not in spy.updates
    assert spy.updates == {
        "jax_persistent_cache_min_compile_time_secs": 0.0}


def test_cache_env_unset_is_repo_jax_cache_from_any_cwd(monkeypatch,
                                                        tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    seen = []
    for cwd in (tmp_path, REPO / "tests"):
        monkeypatch.chdir(cwd)
        seen.append(cli.compile_cache_dir())
    assert seen == [str(REPO / ".jax_cache")] * 2
    spy = _ConfigSpy(monkeypatch, "tpu")
    cli.enable_compile_cache()
    assert spy.updates == {
        "jax_compilation_cache_dir": seen[0],
        "jax_persistent_cache_min_compile_time_secs": 0.0}


def test_cache_unset_env_cpu_run_stays_uncached(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    spy = _ConfigSpy(monkeypatch, "cpu")
    cli.enable_compile_cache()
    assert spy.updates == {}


def test_cache_code_knows_no_home_and_no_private_env():
    src = (inspect.getsource(cli.compile_cache_dir)
           + inspect.getsource(cli.enable_compile_cache))
    for gone in ("FEDML_TPU_CACHE", "~/.cache", "expanduser", "HOME",
                 "getpid", "tempfile"):
        assert gone not in src, gone
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


# -- chip_smoke.py -----------------------------------------------------------

def test_chip_smoke_on_a_cpu_host_exits_nonzero_and_names_the_platform(
        capsys):
    """`python chip_smoke.py` is `sys.exit(main())`; on this CPU-only
    host main() names the platform, runs nothing and returns 1."""
    import chip_smoke
    assert chip_smoke.main() == 1
    lines = capsys.readouterr().out.strip().splitlines()
    assert "platform: cpu" in lines and f"jax {jax.__version__}" in lines
    assert "not a TPU" in lines[-1] and "'cpu'" in lines[-1]
    assert not any(line.startswith("{") for line in lines)  # no result
    assert 'sys.exit(main())' in (REPO / "chip_smoke.py").read_text()


def test_chip_smoke_never_chooses_its_own_platform():
    src = (REPO / "chip_smoke.py").read_text()
    for hidden in ("jax_platforms", "JAX_PLATFORMS", '"--platform"',
                   "interpret=True", "host_device_count"):
        assert hidden not in src, hidden
    # the result line is the driver's contract
    assert '{"ok": True' in src and '"count": len(devices)' in src


# -- compiled or interpreted: one helper -------------------------------------

@pytest.mark.parametrize("backend,want", [("tpu", False), ("cpu", True)])
def test_pallas_interpret_follows_the_platform(monkeypatch, caplog,
                                               backend, want):
    from fedml_tpu.core.pallas_agg import pallas_interpret
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with caplog.at_level("INFO", logger="fedml_tpu.core.pallas_agg"):
        assert pallas_interpret("robust_aggregate") is want
    assert caplog.records[-1].args == (
        "robust_aggregate", "interpreted" if want else "compiled")


def test_pallas_interpret_refuses_an_unknown_platform(monkeypatch):
    from fedml_tpu.core.pallas_agg import pallas_interpret
    monkeypatch.setattr(jax, "default_backend", lambda: "mystery")
    with pytest.raises(RuntimeError, match="mystery"):
        pallas_interpret("secagg_mask")


@pytest.mark.parametrize("backend,interpret", [("tpu", False),
                                               ("cpu", True)])
def test_every_kernel_selector_asks_the_helper(monkeypatch, backend,
                                               interpret):
    """--secagg_backend pallas, --fused_finalize and --defense_backend
    pallas all take their interpret flag from the one helper: on a TPU
    none of them can come out interpreted."""
    from fedml_tpu.secure.secagg import SecureCohortAggregator
    from fedml_tpu.shard_spine import build_shard_spine
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert SecureCohortAggregator(4, backend="pallas")._interpret \
        is interpret
    tmpl = {"w": np.zeros((64, 128), np.float32)}
    auto = build_shard_spine(tmpl, num_shards=2, mesh=None).agg
    assert auto.fused is (backend == "tpu")     # auto: the kernel on a TPU
    forced = build_shard_spine(tmpl, num_shards=2, mesh=None,
                               fused="on").agg
    assert forced.fused and forced.interpret is interpret
    import fedml_tpu.algorithms.fedavg_robust as robust
    assert "interpret=pallas_interpret(" in inspect.getsource(robust)


def test_no_backend_name_test_is_left_on_the_main_path():
    """No donation guard by backend name, and no interpret-by-name."""
    hits = []
    for path in (REPO / "fedml_tpu").rglob("*.py"):
        text = path.read_text()
        for needle in ('default_backend() != "cpu"',
                       'default_backend() != "tpu"'):
            if needle in text:
                hits.append((path.name, needle))
    # the two that remain REFUSE a config (flash attention off a TPU);
    # neither selects a quieter path
    assert sorted(hits) == [("models.py", 'default_backend() != "tpu"'),
                            ("transformer.py",
                             'default_backend() != "tpu"')], hits


# -- flash attention: refused at config time, not inside a jit ---------------

def test_attn_flash_is_refused_at_config_time(monkeypatch):
    from fedml_tpu.experiments.models import create_workload
    with pytest.raises(ValueError, match="'cpu' backend"):
        create_workload("transformer", "shakespeare", 90, (80,),
                        attn_flash=True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="blocks of 128.*80 tokens"):
        create_workload("transformer", "shakespeare", 90, (80,),
                        attn_flash=True)
    wl = create_workload("transformer", "shakespeare", 90, (256,),
                         attn_flash=True)
    assert wl.model.use_flash


# -- where the state lives ---------------------------------------------------

def test_placement_of_counts_devices_and_names_host_trees(devices):
    import jax.numpy as jnp
    from fedml_tpu.parallel.mesh import placement_of
    assert placement_of({"w": np.zeros(3)}) == {"platform": "host",
                                                "devices": 0}
    one = {"w": jnp.zeros(3), "b": jnp.ones(2)}
    assert placement_of(one) == {"platform": "cpu", "devices": 1}
    assert placement_of({**one, "n": np.zeros(1)})["platform"] == "host"
    spread = {"a": jax.device_put(np.zeros(3), devices[1]),
              "b": jax.device_put(np.zeros(3), devices[5])}
    assert placement_of(spread) == {"platform": "cpu", "devices": 2}


def test_ingest_arena_stages_on_the_device_it_is_given(devices):
    from fedml_tpu.comm.ingest import IngestArena
    tmpl = {"w": np.ones((4, 8), np.float32), "b": np.ones(8, np.float32)}
    arena = IngestArena(tmpl, device=devices[3])
    arena.round_start(tmpl)
    screen = arena.stage_tree(jax.tree.map(lambda v: v * 2, tmpl))
    assert screen.structural_ok and screen.finite
    assert screen.sumsq == pytest.approx(40.0)
    where = {d for leaf in jax.tree.leaves(screen.tree)
             for d in leaf.devices()}
    assert where == {devices[3]}


def test_cifar_twin_honours_client_num_in_total():
    """--client_num_in_total reaches the hermetic CIFAR twin (it used to
    be dropped, leaving every twin run at 8 clients)."""
    from fedml_tpu.experiments.config import config_from_argv
    cfg = config_from_argv(["--dataset", "cifar10", "--batch_size", "64",
                            "--client_num_in_total", "10"])
    data = cli.load_experiment_data(cfg)
    assert data.client_num == 10
    assert data.train["x"].shape[:1] + data.train["x"].shape[2:] \
        == (10, 64, 32, 32, 3)


# -- the process-wide compile account ----------------------------------------

def test_compile_totals_count_backend_compiles():
    """`chip_smoke.Phases` reads the program's own compile account
    (chip_smoke keeps no listener of its own)."""
    import jax.numpy as jnp
    import chip_smoke
    from fedml_tpu.obs.trace import compile_totals
    phases = chip_smoke.Phases()
    before = compile_totals()
    with phases("tiny"):
        jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()
    after = compile_totals()
    assert after["compiles"] > before["compiles"]
    assert after["compile_s"] > before["compile_s"]
    assert after["trace_s"] > before["trace_s"]
    assert after["lower_s"] > before["lower_s"]
    [row] = phases.rows
    assert row["compiles"] == after["compiles"] - before["compiles"]
    assert not hasattr(chip_smoke, "CompileWatch")
    assert json.dumps(after)  # plain numbers: printable as a fact


def test_the_selected_phase_runs_at_a_tiny_size_on_the_cpu(capsys,
                                                            monkeypatch):
    """`chip_smoke.phase_selected` is the chip's check of the indexed
    attention at its published widths; here its control flow at the tiny
    configuration's (no platform is asserted inside it).  The kernels'
    check it ends with needs the chip (the tiny heads are too narrow for
    them): here it is handed the selection the phase made, and
    `tests/test_fused_attention.py` runs the same comparison through the
    interpreter."""
    import chip_smoke
    from unittest import mock
    check = mock.Mock()
    monkeypatch.setattr(chip_smoke, "check_selected_kernels", check)
    kernels = object()
    chip_smoke.phase_selected(
        kernels, "benchmark/tests/tiny/models/keye_vl2_30b_a3b.json", t=64,
        block=16)
    said = capsys.readouterr().out
    assert "program vs plain reference" in said
    assert said.count("pairs chosen by one and not the other") == 2
    tiny = json.load(open(REPO / "benchmark" / "tests" / "tiny" / "models"
                          / "keye_vl2_30b_a3b.json"))
    (got, selected, *widths, block), _ = check.call_args
    assert got is kernels and selected.shape == (1, 64, 64)
    assert widths == [tiny["num_attention_heads"],
                      tiny["num_key_value_heads"], tiny["head_dim"]]
    assert block == 16 and selected.dtype == bool
