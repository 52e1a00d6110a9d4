"""Multi-host execution: `jax.distributed.initialize` actually running.

The reference launches N+1 OS processes via mpirun + hostfile
(run_fedavg_distributed_pytorch.sh:17-21).  The TPU replacement is
`init_distributed` (parallel/mesh.py) — every host runs the same program,
`jax.devices()` spans all hosts, collectives ride ICI/DCN.  These tests
execute that path for real: TWO separate OS processes on localhost, a
shared coordinator, one global [clients] mesh with one device per process,
and a full cohort training round whose psum-aggregated result must be
bit-identical on both processes.
"""

import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = r"""
import sys
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
from fedml_tpu.parallel.mesh import init_distributed, make_mesh, stage_global
assert init_distributed(f"127.0.0.1:{{port}}", nproc, pid)
assert jax.process_count() == nproc
assert jax.device_count() == nproc        # one CPU device per process

import hashlib
import numpy as np
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from fedml_tpu.data.stacking import stack_client_data
from fedml_tpu.models import LogisticRegression
from fedml_tpu.parallel.cohort import make_cohort_step
from fedml_tpu.trainer.local_sgd import make_local_trainer
from fedml_tpu.trainer.workload import (ClassificationWorkload,
                                        make_client_optimizer)

n_dev = jax.device_count()
mesh = make_mesh(client_axis=n_dev)
rng = np.random.RandomState(0)   # same seed everywhere: every process
xs = [rng.randn(8, 12).astype(np.float32) for _ in range(n_dev)]
ys = [rng.randint(0, 3, 8).astype(np.int32) for _ in range(n_dev)]
stacked = stack_client_data(xs, ys, batch_size=4)
wl = ClassificationWorkload(LogisticRegression(12, 3), num_classes=3)
local = make_local_trainer(wl, make_client_optimizer("sgd", 0.1), epochs=1)
step = make_cohort_step(local, mesh=mesh)
params = wl.init(jax.random.key(0), jax.tree.map(
    lambda v: jnp.asarray(v[0, 0]),
    {{k: stacked[k] for k in ("x", "y", "mask")}}))
new_params, _ = step(stage_global(params, mesh),
                     stage_global(stacked, mesh, P("clients")),
                     stage_global(jax.random.key(1), mesh))
jax.block_until_ready(new_params)
host = jax.tree.map(lambda a: np.asarray(jax.device_get(a)), new_params)
moved = max(float(abs(np.asarray(a - b)).max())
            for a, b in zip(jax.tree.leaves(host), jax.tree.leaves(params)))
assert moved > 0, "training round did not update parameters"
digest = hashlib.sha256(b"".join(
    np.ascontiguousarray(l).tobytes()
    for l in jax.tree.leaves(host))).hexdigest()
print(f"DIGEST {{pid}} {{digest}}", flush=True)
"""


_WORKER_2LEVEL = r"""
import sys
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
from fedml_tpu.parallel.mesh import (init_distributed, make_two_level_mesh,
                                     stage_global)
assert init_distributed(f"127.0.0.1:{{port}}", nproc, pid)
assert jax.process_count() == nproc
assert jax.device_count() == nproc * 4    # four local devices per process

import hashlib
import numpy as np
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from fedml_tpu.algorithms.hierarchical import (make_grouped_round,
                                               make_two_level_round)
from fedml_tpu.data.stacking import stack_client_data
from fedml_tpu.models import LogisticRegression
from fedml_tpu.trainer.local_sgd import make_local_trainer
from fedml_tpu.trainer.workload import (ClassificationWorkload,
                                        make_client_optimizer)

# two-level [groups=nproc, clients=4] global mesh: jax.devices() orders
# process 0's four local devices first, so the groups axis IS the process
# (DCN) boundary and the clients axis stays process-local (the ICI tier)
mesh = make_two_level_mesh(group_axis=nproc, client_axis=4)
assert [d.process_index for d in mesh.devices[pid]] == [pid] * 4

G, M = nproc, 4
rng = np.random.RandomState(0)   # same seed everywhere: every process
xs = [rng.randn(8, 12).astype(np.float32) for _ in range(G * M)]
ys = [rng.randint(0, 3, 8).astype(np.int32) for _ in range(G * M)]
flat = stack_client_data(xs, ys, batch_size=4)
cohorts = jax.tree.map(
    lambda v: v.reshape((G, M) + v.shape[1:]), flat)  # [G, M, S, B, ...]
wl = ClassificationWorkload(LogisticRegression(12, 3), num_classes=3)
local = make_local_trainer(wl, make_client_optimizer("sgd", 0.1), epochs=1)
params = wl.init(jax.random.key(0), jax.tree.map(
    lambda v: jnp.asarray(v[0, 0]),
    {{k: flat[k] for k in ("x", "y", "mask")}}))

two = make_two_level_round(local, group_comm_round=2, mesh=mesh)
out = two(stage_global(params, mesh),
          stage_global(cohorts, mesh, P("groups", "clients")),
          stage_global(jax.random.key(1), mesh))
jax.block_until_ready(out)
host = jax.tree.map(lambda a: np.asarray(jax.device_get(a)), out)

# single-process oracle: the vmapped simulation twin on local data only —
# no collectives, so it needs nothing from the other process
sim = jax.tree.map(np.asarray, make_grouped_round(local, 2)(
    params, jax.tree.map(jnp.asarray, cohorts), jax.random.key(1)))
err = max(float(abs(a - b).max())
          for a, b in zip(jax.tree.leaves(host), jax.tree.leaves(sim)))
assert err < 1e-5, f"two-level pod round != single-process sim ({{err}})"

digest = hashlib.sha256(b"".join(
    np.ascontiguousarray(l).tobytes()
    for l in jax.tree.leaves(host))).hexdigest()
print(f"DIGEST {{pid}} {{digest}}", flush=True)
"""


_WORKER_SCAFFOLD = r"""
import sys
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
from fedml_tpu.parallel.mesh import init_distributed, make_mesh
assert init_distributed(f"127.0.0.1:{{port}}", nproc, pid)
assert jax.process_count() == nproc
assert jax.device_count() == nproc * 4    # four local devices per process

import hashlib
import numpy as np
import jax.numpy as jnp
from fedml_tpu.algorithms.scaffold import Scaffold, ScaffoldConfig
from fedml_tpu.data.stacking import FederatedData, stack_client_data
from fedml_tpu.models import LogisticRegression
from fedml_tpu.trainer.workload import ClassificationWorkload

n_dev = jax.device_count()
mesh = make_mesh(client_axis=n_dev)
rng = np.random.RandomState(0)   # same seed everywhere: every process
xs = [rng.randn(8, 12).astype(np.float32) for _ in range(n_dev)]
ys = [rng.randint(0, 3, 8).astype(np.int32) for _ in range(n_dev)]
train = stack_client_data(xs, ys, batch_size=4)
data = FederatedData(client_num=n_dev, class_num=3, train=train, test=train)
wl = ClassificationWorkload(LogisticRegression(12, 3), num_classes=3)
cfg = dict(comm_round=3, client_num_per_round=n_dev, epochs=1,
           batch_size=4, lr=0.1, frequency_of_the_test=100)

# the mesh run crosses the process boundary (psum over clients; the
# updated control variates come back replicated via the wrap's
# all_gather, so BOTH processes scatter identical rows into their
# host-resident state mirrors)
algo = Scaffold(wl, data, ScaffoldConfig(**cfg), mesh=mesh)
p_mesh = algo.run(rng=jax.random.key(7))
jax.block_until_ready(p_mesh)
host = jax.tree.map(lambda a: np.asarray(jax.device_get(a)), p_mesh)
c_locals_host = jax.tree.map(np.asarray, algo.c_locals)

# single-chip oracle runs locally in the same worker (no collectives):
# multi-process mesh must match it leaf-for-leaf, per-client state too
solo = Scaffold(wl, data, ScaffoldConfig(**cfg))
p_solo = jax.tree.map(np.asarray, solo.run(rng=jax.random.key(7)))
err = max(float(abs(a - b).max())
          for a, b in zip(jax.tree.leaves(host), jax.tree.leaves(p_solo)))
assert err < 1e-5, f"scaffold 2-proc mesh != single-chip params ({{err}})"
err_c = max(float(abs(np.asarray(a) - np.asarray(b)).max())
            for a, b in zip(jax.tree.leaves(c_locals_host),
                            jax.tree.leaves(solo.c_locals)))
assert err_c < 1e-5, f"scaffold 2-proc control variates diverged ({{err_c}})"

# Ditto on the same cluster: the one caller that passes a single
# (non-tuple) out_specs P("clients") to make_sharded_stateful_round, so
# this exercises the wrap's single-spec gather/eff_out branch for real
from fedml_tpu.algorithms.ditto import Ditto, DittoConfig
d_cfg = dict(cfg)
d_algo = Ditto(wl, data, DittoConfig(**d_cfg, ditto_lambda=0.1), mesh=mesh)
d_mesh = d_algo.run(rng=jax.random.key(11))
jax.block_until_ready(d_mesh)
d_host = jax.tree.map(lambda a: np.asarray(jax.device_get(a)), d_mesh)
v_host = jax.tree.map(np.asarray, d_algo.v_locals)

d_solo = Ditto(wl, data, DittoConfig(**d_cfg, ditto_lambda=0.1))
d_ref = jax.tree.map(np.asarray, d_solo.run(rng=jax.random.key(11)))
err_d = max(float(abs(a - b).max())
            for a, b in zip(jax.tree.leaves(d_host),
                            jax.tree.leaves(d_ref)))
assert err_d < 1e-5, f"ditto 2-proc mesh != single-chip params ({{err_d}})"
err_v = max(float(abs(np.asarray(a) - np.asarray(b)).max())
            for a, b in zip(jax.tree.leaves(v_host),
                            jax.tree.leaves(d_solo.v_locals)))
assert err_v < 1e-5, f"ditto 2-proc personal models diverged ({{err_v}})"

digest = hashlib.sha256(b"".join(
    np.ascontiguousarray(l).tobytes()
    for l in jax.tree.leaves(host) + jax.tree.leaves(c_locals_host)
    + jax.tree.leaves(d_host) + jax.tree.leaves(v_host))).hexdigest()
print(f"DIGEST {{pid}} {{digest}}", flush=True)
"""


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_distributed_round(tmp_path):
    """2 OS processes x 1 CPU device: init_distributed wires a global mesh,
    the federated round's psum aggregation crosses the process boundary,
    and both processes finish with the SAME global model."""
    script = tmp_path / "worker.py"
    script.write_text(_WORKER.format(repo=REPO))
    port = _free_port()
    env = dict(os.environ)
    # one local device per process — scrub the parent suite's virtual-mesh
    # flag so the device count measured is the distributed one
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(pid), "2", str(port)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for pid in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
            assert p.returncode == 0, out
    finally:
        for p in procs:  # a worker stuck at the coordinator barrier must
            p.kill()     # not outlive the test holding the port

    digests = sorted(line.split()[2] for out in outs
                     for line in out.splitlines()
                     if line.startswith("DIGEST"))
    assert len(digests) == 2 and digests[0] == digests[1], outs


@pytest.mark.slow
def test_two_process_four_device_hierarchical_round(tmp_path):
    """2 OS processes x 4 virtual CPU devices each: the two-level
    [groups=2, clients=4] mesh puts the groups axis exactly on the
    process (DCN) boundary and the clients axis process-local (ICI).  A
    full hierarchical round — 2 group-local FedAvg rounds + global
    weighted psum across processes — must match the single-process
    vmapped simulation leaf-for-leaf and agree bit-identically between
    the processes."""
    script = tmp_path / "worker2.py"
    script.write_text(_WORKER_2LEVEL.format(repo=REPO))
    port = _free_port()
    env = dict(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append("--xla_force_host_platform_device_count=4")
    env["XLA_FLAGS"] = " ".join(flags)
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(pid), "2", str(port)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for pid in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
            assert p.returncode == 0, out
    finally:
        for p in procs:
            p.kill()

    digests = sorted(line.split()[2] for out in outs
                     for line in out.splitlines()
                     if line.startswith("DIGEST"))
    assert len(digests) == 2 and digests[0] == digests[1], outs


@pytest.mark.slow
def test_two_process_four_device_scaffold_round(tmp_path):
    """2 OS processes x 4 virtual CPU devices: STATEFUL algorithms on a
    multi-process [clients=8] mesh (round-4 verdict item 4).  SCAFFOLD
    (tuple out_specs) and Ditto (the single non-tuple out_specs caller,
    covering the wrap's other gather branch), three rounds each with
    host-resident per-client state: inputs staged global, state outputs
    all_gather-replicated, every process scatters the same rows into its
    own mirror.  Both must match the single-chip run leaf-for-leaf
    (params AND per-client state) and agree bit-identically between the
    processes."""
    script = tmp_path / "worker_scaffold.py"
    script.write_text(_WORKER_SCAFFOLD.format(repo=REPO))
    port = _free_port()
    env = dict(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append("--xla_force_host_platform_device_count=4")
    env["XLA_FLAGS"] = " ".join(flags)
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(pid), "2", str(port)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for pid in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
            assert p.returncode == 0, out
    finally:
        for p in procs:
            p.kill()

    digests = sorted(line.split()[2] for out in outs
                     for line in out.splitlines()
                     if line.startswith("DIGEST"))
    assert len(digests) == 2 and digests[0] == digests[1], outs
