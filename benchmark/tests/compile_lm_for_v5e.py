"""`compile_for_v5e.py` for a language-model cell (a ``--model_config``
configuration on token shards): compile its wave program, the summed wave
`CrossDevice` runs for a GB-size tree, at its real size for a described
(not attached) TPU v5e and print the compiler's memory analysis and the
number of `while` ops in the compiled text (an indexed attention's search
loops among them: once a layer and searched block when the selection is
made once a step).  Costs no chip time; nothing runs.

    JAX_PLATFORMS=cpu python benchmark/tests/compile_lm_for_v5e.py <workload> [steps a silo]
"""
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.chdir(ROOT)


def main(workload, steps):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from benchmark import run
    from fedml_tpu.device_cohort import make_summed_wave_fn
    from fedml_tpu.experiments.models import create_workload
    from fedml_tpu.parallel.cohort import train_cohort_sum
    from fedml_tpu.trainer.local_sgd import make_local_trainer
    from fedml_tpu.trainer.workload import make_client_optimizer
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = run.Cell(bench, workload)
    a, m = cell.cli, cell.config["model"]
    T, W, B = m["seq_len"], int(a["wave_size"]), int(a["batch_size"])
    wl = create_workload(a["model"], a["dataset"], m["vocab_held"], (T,),
                         attn_block_size=int(a["attn_block_size"]),
                         model_config=a["model_config"])
    local = make_local_trainer(
        wl, make_client_optimizer(a["client_optimizer"], float(a["lr"])),
        int(a["epochs"]))

    def train_summed(params, wave_data, rng, offset):
        wave_sum, total, metrics = train_cohort_sum(
            local, params, wave_data, rng, index_offset=offset)
        return wave_sum, total, metrics.get("counters", {})
    wave_fn = make_summed_wave_fn(train_summed)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=chip)
    params = jax.tree.map(
        lambda v: sds(v.shape, v.dtype),
        jax.eval_shape(lambda: wl.init(
            jax.random.key(0), {"x": jnp.zeros((B, T), jnp.int32)})))
    key = jax.eval_shape(lambda: jax.random.key(0))
    data = {"x": sds((W, steps, B, T), jnp.int32),
            "y": sds((W, steps, B, T), jnp.int32),
            "mask": sds((W, steps, B), jnp.float32),
            "num_samples": sds((W,), jnp.float32)}
    t0 = time.time()
    compiled = wave_fn.lower(params, data, sds(key.shape, key.dtype),
                             sds((), jnp.int32)).compile()
    ma = compiled.memory_analysis()
    print(json.dumps({
        "workload": workload, "wave": W, "steps": steps, "batch": B,
        "parameters": sum(int(np.prod(v.shape))
                          for v in jax.tree.leaves(params)),
        "compile_s": round(time.time() - t0, 1),
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "code_bytes": ma.generated_code_size_in_bytes,
        "while_ops": compiled.as_text().count(" while(")}))

if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 2)
