"""The benchmark's contract with the program (ISSUE 34).

The driver judges every PR through `benchmark/`, and `benchmark/` holds the
program by NAME: `hooks/*.json` names private functions to wrap and the
wave program's module, `layer_metrics/*.json` names spans, span args,
ledger phases and jit modules, `configs/*.json` and `traffic/*.json` name
CLI flags.  A rename that passes every other test dies on the chip as
`output_malformed`.  Each file (and each name of a hooks file) is one case
here, read from the benchmark's own data; nothing under `benchmark/` is
edited by this test, and a name that moves in the program fails its case.
"""

import dataclasses
import glob
import inspect
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from benchmark.probe import resolve
from benchmark.run import Cell, call
from fedml_tpu.experiments.config import ExperimentConfig, config_from_argv

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


def _files(sub):
    return {os.path.basename(p)[:-len(".json")]: json.load(open(p))
            for p in sorted(glob.glob(os.path.join(BENCH, sub, "*.json")))}


HOOKS = _files("hooks")
LAYER_METRICS = _files("layer_metrics")
CLI_FILES = {f"{sub}/{name}": spec for sub in ("configs", "traffic")
             for name, spec in _files(sub).items()}
BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))

# of `cli_run`'s two runs (tests/conftest.py), the one with every timing
# site live
all_sites_live = pytest.mark.parametrize("cli_run", ["ingest_pipeline"],
                                         indirect=True)


# ---------------------------------------------------------------------------
# (a) hooks: what the harness wraps, and the wave program's module name
# ---------------------------------------------------------------------------

def _module_name(lowered) -> str:
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


@pytest.fixture(scope="module")
def program_modules():
    """Module names of the engines' compiled programs, the wave program
    first, by the name of the hooks file (`--algo`): what the trace
    reduction finds programs by."""
    from fedml_tpu.algorithms.cross_device import (CrossDevice,
                                                   CrossDeviceConfig)
    from fedml_tpu.core import global_crc
    from fedml_tpu.core.stream_agg import (StreamingAggregator,
                                           zeros_acc_like)
    from fedml_tpu.data import load_data
    from fedml_tpu.data.stacking import gather_cohort
    from fedml_tpu.experiments.models import (create_workload,
                                              sample_shape_of)
    data = load_data("mnist", data_dir=None, batch_size=4, num_clients=8,
                     seed=0)
    workload = create_workload("lr", "mnist", data.class_num,
                               sample_shape_of(data))
    eng = CrossDevice(workload, data, CrossDeviceConfig(
        comm_round=1, client_num_per_round=8, epochs=1, batch_size=4,
        wave_size=5, seed=0))
    params = jax.tree.map(jnp.asarray, workload.init(
        jax.random.key(0), jax.tree.map(
            lambda v: v[0, 0],
            {k: data.train[k] for k in ("x", "y", "mask")})))
    wave = eng._wave_fn.lower(
        params, gather_cohort(data.train, [1, 2, 3], pad_to=5),
        jax.random.key(1), jnp.int32(0))
    agg = StreamingAggregator(params)
    acc = zeros_acc_like(params)
    stacked = jax.tree.map(lambda p: jnp.stack([p] * 5), params)
    fold = agg._fold_wave_fn.lower(acc, jnp.float32(0), stacked,
                                   jnp.ones(5, jnp.float32), params)
    finalize = agg._finalize_fn.lower(acc, jnp.float32(1), params, 0)
    # the global's CRC, as `global_crc.TreeCrc` builds it for this tree
    crc_fn, consts = global_crc.program(jax.tree.leaves(params), None)
    crc = jax.jit(crc_fn).lower(jax.tree.leaves(params), consts)
    return {"cross_device": [_module_name(p)
                             for p in (wave, fold, finalize, crc)]}


def _hook_cases():
    for algo, hooks in HOOKS.items():
        yield pytest.param(algo, "round_hook", id=f"{algo}-round_hook")
        for span in hooks.get("spans", {}):
            yield pytest.param(algo, span, id=f"{algo}-spans.{span}")


@pytest.mark.parametrize("algo,key", _hook_cases())
def test_hook_target_resolves_to_a_callable(algo, key):
    hooks = HOOKS[algo]
    target = (hooks["round_hook"]["target"] if key == "round_hook"
              else hooks["spans"][key])
    owner, name = resolve(target)       # the harness's own resolver
    fn = getattr(owner, name)
    assert callable(fn), target
    if key == "round_hook":
        # the harness reads the global and the cohort from `args[i]` of
        # the call, `self` included: both lie among the positional
        # parameters
        positional = [
            p for p in inspect.signature(fn).parameters.values()
            if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)]
        for arg in ("state_arg", "cohort_arg"):
            assert 0 < hooks["round_hook"][arg] < len(positional), arg


@pytest.mark.parametrize("algo", sorted(HOOKS))
def test_wave_program_is_the_wave_jits_module_name(algo, program_modules):
    assert HOOKS[algo]["wave_program"] == program_modules[algo][0]


# ---------------------------------------------------------------------------
# (b) layer metrics: every name a reader is given occurs in a run
# ---------------------------------------------------------------------------

def _span_args(events, name):
    return set().union(*(e["args"] for e in events if e["name"] == name))


def _check_metric_args(args, cli_run, modules):
    """What each arg of a `layer_metrics` file names in the program."""
    events, ledger = cli_run["events"], cli_run["ledger"]
    names = {e["name"] for e in events}
    for key, value in args.items():
        if key in ("name", "less"):
            assert value in names, f"no span {value!r} in trace.json"
        elif key in ("part", "whole"):
            assert value in _span_args(events, args["name"]), \
                f"span {args['name']!r} carries no arg {value!r}"
        elif key == "of_round":
            assert any(e["name"] == "round" and e["args"]["round"] == value
                       for e in events), f"no `round` span of round {value}"
        elif key == "phases":
            seen = set().union(*(line["phases"] for line in ledger))
            assert set(value) <= seen, \
                f"perf.jsonl holds no phase {set(value) - seen}"
        elif key == "modules":
            assert set(value) <= modules, \
                f"no program compiles as {set(value) - modules}"
        else:
            # the harness's own (a percentile, a key of its trace
            # reduction): names nothing of the program
            assert key in ("q", "key"), \
                f"arg {key!r}: say here what it names in the program"


@all_sites_live
@pytest.mark.parametrize("metric", sorted(LAYER_METRICS))
def test_layer_metric_reads_names_the_program_writes(metric, cli_run,
                                                     program_modules):
    spec = LAYER_METRICS[metric]
    assert callable(call(spec["reader"])), spec["reader"]
    modules = {m for names in program_modules.values() for m in names}
    _check_metric_args(spec.get("args", {}), cli_run, modules)


# ---------------------------------------------------------------------------
# (c) configurations and traffic: CLI flags
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("path", sorted(CLI_FILES))
def test_cli_keys_are_config_fields(path):
    fields = {f.name for f in dataclasses.fields(ExperimentConfig)}
    assert set(CLI_FILES[path]["cli"]) <= fields, \
        set(CLI_FILES[path]["cli"]) - fields


@pytest.mark.parametrize("workload",
                         [w["name"] for w in BENCHMARK["workloads"]])
def test_workload_flags_pass_the_cli_parser(workload):
    """The argv `benchmark/run.py` hands `main()` for the cell, built by
    the harness's own `Cell.argv`, parses into the values the files give."""
    cell = Cell(BENCHMARK, workload)
    cfg = config_from_argv(cell.argv(seed=1, data_dir="d", run_dir="r",
                                     rounds=2))
    for key, value in cell.cli.items():
        if key != "seed":           # a configuration may pin it
            assert getattr(cfg, key) == value, key
