"""Device-mesh construction — the TPU replacement for mpirun + hostfile +
gpu_mapping.yaml (fedml_api/distributed/utils/gpu_mapping.py:8-37).

The reference assigns one OS process per FL participant and places each on a
GPU via a YAML table.  Here, placement is a `jax.sharding.Mesh`: the
``clients`` axis shards the cohort across chips; an optional ``model`` axis
gives intra-client model sharding (pjit tensor-parallel "for free" — a config
knob, not an algorithm, per SURVEY.md §2.5).  Multi-host pods initialize with
`jax.distributed.initialize` and the same code runs unchanged; hierarchical
FL maps its group tier onto ICI within a slice and its global tier onto DCN
across slices (two-level mesh axes)."""

from __future__ import annotations

from typing import Any, Optional, Sequence

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(client_axis: Optional[int] = None, model_axis: int = 1,
              devices: Optional[Sequence[jax.Device]] = None,
              axis_names=("clients", "model")) -> Mesh:
    """Mesh over all (or given) devices: [clients, model].

    Defaults: every device on the clients axis, no model sharding."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if model_axis < 1:
        raise ValueError(
            f"cannot build a mesh with model_axis={model_axis}: every mesh "
            f"axis must be >= 1 (got {n} devices)")
    if client_axis is None:
        client_axis = n // model_axis
    # a loud, assert-free factorization check: this used to be a bare
    # ``assert`` that vanishes under ``python -O`` and named no remedy —
    # a mis-factored launch must fail the same way in every interpreter
    # mode (the repo's fail-loudly convention)
    if client_axis < 1 or client_axis * model_axis != n:
        raise ValueError(
            f"cannot build a [{client_axis}, {model_axis}] "
            f"({axis_names[0]} x {axis_names[1]}) mesh from {n} devices: "
            f"the axes must be >= 1 and their product must equal the "
            f"device count — pass axis sizes that factor {n}, or a "
            f"matching devices= subset")
    arr = np.asarray(devices).reshape(client_axis, model_axis)
    return Mesh(arr, axis_names)


def make_two_level_mesh(group_axis: int, client_axis: Optional[int] = None,
                        devices: Optional[Sequence[jax.Device]] = None
                        ) -> Mesh:
    """[groups, clients] mesh for hierarchical FL (SURVEY.md §2.5): the
    group tier aggregates over the ``clients`` axis (ICI within a slice),
    the global tier over the ``groups`` axis (DCN across slices).  On a real
    multi-slice pod pass ``devices`` ordered slice-major so the groups axis
    falls on the DCN boundary."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if group_axis < 1:
        # guard BEFORE the derived division: group_axis=0 used to die as
        # a bare ZeroDivisionError instead of a named config error
        raise ValueError(
            f"cannot build a two-level mesh with group_axis={group_axis}: "
            f"the groups axis must be >= 1 (got {n} devices)")
    if client_axis is None:
        client_axis = n // group_axis
    if client_axis < 1 or group_axis * client_axis != n:
        raise ValueError(
            f"cannot build a [{group_axis}, {client_axis}] two-level mesh "
            f"from {n} devices: the axes must be >= 1 and their product "
            f"must equal the device count — the groups axis must divide "
            f"{n} (pass a client_axis that factors it, or a matching "
            f"devices= subset)")
    arr = np.asarray(devices).reshape(group_axis, client_axis)
    return Mesh(arr, ("groups", "clients"))


def placement_of(tree: Any) -> dict:
    """Which devices hold a pytree's leaves, for a run's summary line:
    ``{"platform": "tpu", "devices": 4}``; ``"host"`` / 0 when any leaf
    is not a `jax.Array` — "tpu" means EVERY leaf is on a TPU."""
    leaves = jax.tree.leaves(tree)
    if not leaves or not all(isinstance(x, jax.Array) for x in leaves):
        return {"platform": "host", "devices": 0}
    devs = set().union(*(x.devices() for x in leaves))
    return {"platform": "+".join(sorted({d.platform for d in devs})),
            "devices": len(devs)}


def make_model_mesh(num_shards: int,
                    devices: Optional[Sequence[jax.Device]] = None
                    ) -> Optional[Mesh]:
    """A ``[1, num_shards]`` (clients x model) mesh for the sharded
    global-model spine (`fedml_tpu.shard_spine`): every shard of the
    round state lives on its own device of the ``model`` axis.  Returns
    None when fewer than ``num_shards`` devices exist — the spine then
    runs placement-free on the default device (same math, no per-device
    memory split), which is the honest posture on a 1-chip host; the
    run's summary says so (``shard_state_devices``)."""
    devices = list(devices if devices is not None else jax.devices())
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    if len(devices) < num_shards:
        return None
    return make_mesh(client_axis=1, model_axis=num_shards,
                     devices=devices[:num_shards])


def tp_shard_params(params: Any, mesh: Mesh, axis: str = "model",
                    min_size: int = 4096) -> Any:
    """GSPMD tensor-parallel placement: put each large kernel's output
    dim on the ``axis`` mesh axis (replicate everything else) and let XLA
    insert the collectives when the (vmapped) training step is jitted over
    the same mesh — dp over ``clients`` x tp over ``axis`` with no manual
    shard_map (SURVEY.md §2.5: tensor parallel is "a config knob, not an
    algorithm").  Works with the PLAIN make_cohort_step (mesh=None form).

    2-D Dense kernels shard the output dim; 3-D DenseGeneral kernels
    (the transformer's [d_model, heads, d_head] q/k/v projections) shard
    the heads dim — the classic Megatron head-parallel split."""
    n = mesh.shape[axis]

    def place(x):
        nd = getattr(x, "ndim", 0)
        if nd == 2 and x.shape[-1] % n == 0 and x.size >= min_size:
            return jax.device_put(x, NamedSharding(mesh, P(None, axis)))
        if nd == 3 and x.size >= min_size:
            # Megatron head-parallel split for DenseGeneral kernels: the
            # in-projections are [d_model, H, dh] (large dim FIRST — shard
            # H at dim 1, column-parallel) and the out-projection is
            # [H, dh, d_model] (large dim LAST — shard H at dim 0,
            # row-parallel).  Discriminating by large-dim position keeps
            # the q/k/v and out splits consistent so XLA needs one psum
            # per attention block, not a reshard.  GSPMD guarantees
            # correctness either way — the spec is a layout hint.
            # Gate on the Megatron shape signature — one STRICTLY large
            # d_model dim at position 0 or -1, two small head dims — so
            # e.g. a Conv1D kernel [k, c_in, c_out] (two comparable large
            # dims) stays replicated instead of sharding a spatial/channel
            # dim, which GSPMD would accept but pay resharding for.
            d0, d1, d2 = x.shape
            if d0 > max(d1, d2):          # [d_model, H, dh] in-projection
                dim = 1
            elif d2 > max(d0, d1):        # [H, dh, d_model] out-projection
                dim = 0
            else:
                dim = None
            if dim is not None and x.shape[dim] % n == 0:
                spec = [None, None, None]
                spec[dim] = axis
                return jax.device_put(x, NamedSharding(mesh, P(*spec)))
        return jax.device_put(x, NamedSharding(mesh, P()))

    return jax.tree.map(place, params)


def client_axis_size(mesh: Optional[Mesh]) -> int:
    if mesh is None:
        return 1
    return mesh.shape["clients"]


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: int = 1, process_id: int = 0) -> bool:
    """Multi-host bootstrap — the TPU replacement for ``mpirun -np N
    -hostfile mpi_host_file`` (run_fedavg_distributed_pytorch.sh:17-21).

    Each host runs the SAME program with its own ``process_id``;
    `jax.distributed.initialize` wires the pod so `jax.devices()` spans all
    hosts and collectives ride ICI/DCN.  Returns True when distributed mode
    was actually initialized (no-op for single-process runs, so the same
    entry point serves laptop simulation and pod launches)."""
    if coordinator_address is None or num_processes <= 1:
        return False
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    return True


def stage_global(tree: Any, mesh: Optional[Mesh], spec: Optional[P] = None):
    """Make host data feedable to a jit over a (possibly multi-process) mesh.

    Single-process: identity — jit accepts host numpy directly.  Multi-
    process (after `init_distributed`): a device on another host is not
    addressable, so process-local arrays cannot enter a global-mesh jit;
    each leaf is rebuilt as a global ``jax.Array`` via
    ``make_array_from_callback``.  The data-staging contract matches the
    rest of the framework: every process holds the SAME host-side dataset
    (the reference ships all data to every MPI rank too, FedAvgAPI.py:60-75)
    and the callback slices out just the shards this process addresses.

    ``spec=None`` replicates (params / rng keys); ``P("clients")`` shards
    the leading cohort axis.

    IDEMPOTENT: a leaf that is already a global (not fully addressable)
    jax.Array — e.g. the previous round's output fed back in, or an
    argument a caller staged earlier — passes through untouched, so
    layered staging (FedAvg.run stages params/cohort/rng; the stateful
    mesh wrap re-stages every positional arg) is safe.
    """
    if mesh is None or jax.process_count() == 1:
        return tree
    sharding = NamedSharding(mesh, spec if spec is not None else P())

    def mk(x):
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            return x  # already global (idempotent staging)
        if hasattr(x, "dtype") and jax.dtypes.issubdtype(
                x.dtype, jax.dtypes.prng_key):
            # typed PRNG keys can't round-trip through numpy; globalize the
            # underlying uint32 data and re-wrap
            data = mk(np.asarray(jax.random.key_data(x)))
            return jax.random.wrap_key_data(data)
        x = np.asarray(x)
        return jax.make_array_from_callback(x.shape, sharding,
                                            lambda idx: x[idx])

    return jax.tree.map(mk, tree)
