"""Rehearsal 3 of the on-chip-measurement guide: compile a cell's wave
program at its real size for a described (not attached) TPU v5e and print
the compiler's memory analysis.  Costs no chip time; nothing runs.

    JAX_PLATFORMS=cpu python benchmark/tests/compile_for_v5e.py <workload>

The program is built as ``CrossDevice._build_wave_fn`` builds it (the
sgd/fedprox branch), from the cell's files, with the client axis the engine
chooses itself; the staged wave's shapes are the loader's (clients padded
to the longest client's step count).
"""

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main(workload: str, steps: int) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from benchmark import run
    from fedml_tpu.device_cohort import make_wave_fn
    from fedml_tpu.experiments.models import create_workload
    from fedml_tpu.parallel.cohort import train_cohort
    from fedml_tpu.trainer.local_sgd import make_local_trainer
    from fedml_tpu.trainer.workload import make_client_optimizer

    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = run.Cell(bench, workload)
    a, m = cell.cli, cell.config["model"]
    shape, W, B = tuple(m["input"]), int(a["wave_size"]), int(a["batch_size"])
    wl = create_workload(a["model"], a["dataset"], m["classes"], shape)
    local = make_local_trainer(
        wl, make_client_optimizer(a["client_optimizer"], float(a["lr"])),
        int(a["epochs"]))

    def make_stacked(params, wave_data, rng, offset):
        # no client axis named: the engine's own choice from the model's
        # shapes, as the cell runs it
        stacked, _ = train_cohort(local, params, wave_data, rng,
                                  index_offset=offset)
        return stacked, {}

    wave_fn = make_wave_fn(make_stacked)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=chip)
    params = jax.tree.map(
        lambda v: sds(v.shape, v.dtype),
        jax.eval_shape(lambda: wl.init(
            jax.random.key(0), {"x": jnp.zeros((B,) + shape)})))
    key = jax.eval_shape(lambda: jax.random.key(0))
    data = {"x": sds((W, steps, B) + shape, jnp.float32),
            "y": sds((W, steps, B), jnp.int32),
            "mask": sds((W, steps, B), jnp.float32),
            "num_samples": sds((W,), jnp.float32)}
    t0 = time.time()
    compiled = wave_fn.lower(params, data, sds(key.shape, key.dtype),
                             sds((), jnp.int32)).compile()
    ma = compiled.memory_analysis()
    print(json.dumps({
        "workload": workload, "wave": W, "steps": steps, "batch": B,
        "compile_s": round(time.time() - t0, 1),
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "code_bytes": ma.generated_code_size_in_bytes}))


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]))
