"""FedAvg — the north-star algorithm, TPU-style.

Capability parity with BOTH reference paradigms in one implementation:

* standalone simulator (fedml_api/standalone/fedavg/fedavg_api.py:40-81):
  sequential Python loop over sampled clients -> here the cohort trains as
  one vmap'd jit program on a single chip;
* MPI distributed (fedml_api/distributed/fedavg/FedAvgAPI.py:20-75 and the
  manager/aggregator choreography): N+1 processes, message passing, barrier
  -> here a `shard_map` over the mesh's ``clients`` axis with psum
  aggregation (pass ``mesh=``).

Round structure parity: deterministic seeded sampling per round
(FedAVGAggregator.client_sampling:89-97), E local epochs of SGD/Adam,
sample-weighted aggregation, eval every ``frequency_of_the_test`` rounds and
on the final round (FedAVGAggregator.test_on_server_for_all_clients:109-163).
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from fedml_tpu.core.sampling import sample_clients
from fedml_tpu.data.stacking import FederatedData, gather_cohort
from fedml_tpu.parallel.cohort import (make_cohort_step, make_device_round,
                                       cohort_eval)
from fedml_tpu.parallel.mesh import stage_global
from fedml_tpu.trainer.local_sgd import make_local_trainer, make_evaluator
from fedml_tpu.trainer.workload import Workload, make_client_optimizer

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class FedAvgConfig:
    """Flag parity with the argparse soup of main_fedavg.py:46-112 (the
    subset with behavioral effect on the algorithm)."""
    comm_round: int = 10
    client_num_per_round: int = 10
    epochs: int = 1
    batch_size: int = 10
    lr: float = 0.03
    client_optimizer: str = "sgd"
    wd: float = 0.0
    frequency_of_the_test: int = 5
    seed: int = 0
    # >1: run that many rounds per device dispatch (lax.scan over rounds,
    # single-chip HBM-resident data only). Amortises host dispatch latency
    # when a round is sub-ms; rng schedule is fold_in(round) instead of the
    # loop path's sequential splits, so trajectories differ (both
    # deterministic). Eval cadence still honored; ignored with a
    # checkpointer (per-round save cadence needs the host loop) or a
    # _server_update hook (per-round host-side server state, e.g. FedOpt).
    rounds_per_dispatch: int = 1
    # execution of the cohort's client axis: None lets the engine pick
    # from the model's shapes (`parallel/cohort.choose_client_axis`:
    # conv models train their clients in sequence with dense convs, the
    # rest concurrently under vmap); "vmap" / "scan" force one — identical
    # results (parity-tested), and what the parity tests set.  No CLI flag
    # reaches it.
    client_axis: Optional[str] = None
    # evaluate_global processes at most this many clients per compiled
    # call (single-chip and mesh-sharded alike).  The all-clients vmap
    # materializes [C, S, B, ...] activations (an NWP model's logits over
    # a 342k-client corpus would be TBs); chunking bounds eval memory at
    # [chunk, ...] and keeps the memmap staging path O(chunk) in host
    # RAM.  0 = never chunk.
    eval_chunk_clients: int = 1024


def sweep_eval_chunks(stacked, chunk: int, run_chunk):
    """THE chunked-eval convention: slice the stacked client axis into
    [chunk]-row pieces, zero-pad the last one to the static chunk shape
    via pad_clients (padded rows carry mask 0 / weight 0 and contribute
    nothing), call ``run_chunk(part, lo) -> summed-metric dict`` on each,
    and sum.  Summed metric dicts are exact under chunking.  Shared by
    FedAvg.evaluate_global and Ditto.evaluate_personalized — any change
    to the padding contract happens here once."""
    from fedml_tpu.parallel.cohort import pad_clients
    total = None
    n_clients = stacked["num_samples"].shape[0]
    for lo in range(0, n_clients, chunk):
        part = {k: jax.numpy.asarray(np.asarray(v[lo:lo + chunk]))
                for k, v in stacked.items()}
        part = pad_clients(part, chunk)  # static shape across chunks
        m = jax.tree.map(np.asarray, run_chunk(part, lo))
        total = m if total is None else jax.tree.map(
            lambda a, b: a + b, total, m)
    return total


# -- stacked per-client persistent state -----------------------------------
# Algorithms with per-client state that outlives a round (SCAFFOLD control
# variates, Ditto personalized models, FedDyn lambdas) keep it as ONE
# stacked pytree [client_num_in_total, ...] of HOST numpy buffers — at
# cross-device scale the full state cannot live in HBM (342k stackoverflow
# clients x even a 40 KB model is ~14 GB), so only the sampled cohort's
# rows ride to the device each round, mirroring how the DATA corpus stays
# host/memmap-resident (data/stacking.py).  These helpers are THE
# convention: padded cohort slots alias client 0 via the zero-filled id
# vector, so round steps must freeze padded rows (live mask) before the
# scatter — which writes live rows only.


def zeros_client_state(template, client_num: int):
    """A zeroed stacked state tree: one HOST (numpy) row per client,
    shaped like ``template`` (checkpoint templates use this too)."""
    return jax.tree.map(
        lambda x: np.zeros((client_num,) + x.shape, x.dtype), template)


def gather_client_rows(stacked_tree, ids, pad_to: int):
    """The cohort's rows of a stacked per-client state tree, uploaded as
    device arrays; the id vector is zero-padded to the cohort's static
    width (padded slots alias client 0 — consumers freeze them via the
    cohort's live mask)."""
    padded = np.zeros(pad_to, np.int32)
    padded[:len(ids)] = np.asarray(ids, np.int32)
    return jax.tree.map(
        lambda v: jax.numpy.asarray(np.asarray(v)[padded]), stacked_tree)


def scatter_client_rows(stacked_tree, ids, new_rows):
    """Write the LIVE cohort rows back into the host-resident stacked
    state IN PLACE (padded rows are dropped, so an aliased client-0 slot
    cannot clobber real state).  Returns the same buffers for the
    ``state = scatter_client_rows(state, ...)`` idiom."""
    idx = np.asarray(ids, np.int64)
    live_n = len(ids)

    def _write(v, nv):
        v = np.asarray(v)
        v[idx] = np.asarray(nv)[:live_n]
        return v

    return jax.tree.map(_write, stacked_tree, new_rows)


class FedAvg:
    def __init__(self, workload: Workload, data: FederatedData,
                 config: FedAvgConfig, mesh=None, sink=None,
                 local_train=None):
        """``local_train`` overrides the client trainer while keeping ALL of
        FedAvg's execution machinery — including the HBM-resident device
        round and the scanned multi-round dispatch, which subclasses that
        replace ``cohort_step`` wholesale forfeit.  FedProx uses it (its
        only delta is the prox term inside local SGD)."""
        self.workload = workload
        self.data = data
        self.cfg = config
        self.mesh = mesh
        self.sink = sink  # optional MetricsSink: per-round wandb-style log
        if mesh is not None:
            n_dev = mesh.shape["clients"]
            if config.client_num_per_round % n_dev:
                raise ValueError(
                    f"client_num_per_round={config.client_num_per_round} "
                    f"must be a multiple of the mesh clients axis ({n_dev})")
        if local_train is None:
            opt = make_client_optimizer(config.client_optimizer, config.lr,
                                        config.wd)
            local_train = make_local_trainer(workload, opt, config.epochs)
        self._local_train = local_train
        self.cohort_step = make_cohort_step(local_train, mesh=mesh,
                                            client_axis=config.client_axis)
        self._base_cohort_step = self.cohort_step  # fast-path eligibility
        # optional server-side hook applied AFTER each round's aggregation:
        # server_update(prev_params, w_avg) -> new_params (FedOpt's
        # pseudo-gradient optimizer).  Runs outside the round jit, so the
        # HBM-resident device path still serves hooked algorithms; the
        # scanned multi-round path cannot (the hook is per-round host state)
        # and is gated off when set.
        self._server_update = None
        # subclasses whose whole round is custom (FedNova) can still ride
        # the HBM-resident path by providing their own device round with
        # the make_device_round signature (params, stacked, ids, live, rng)
        self._device_round_override = None
        # single-chip fast path: dataset resident in HBM, cohort gathered
        # by ids inside the jit (see make_device_round); built lazily on
        # first run, only when the stacked data fits on device
        self._device_round = None
        self._train_dev = None
        self._test_dev = None  # eval-split device cache (mirrors _train_dev)
        self.evaluate = make_evaluator(workload)
        # global eval over ALL clients rides the mesh too (each device
        # evaluates its shard of clients; metric psum over ICI)
        self._eval_cohort = cohort_eval(self.evaluate, mesh=mesh)
        self.history: List[Dict[str, Any]] = []

    def _sample_round(self, round_idx: int):
        """Cohort ids for one round — the reference's deterministic seeded
        chain (FedAVGAggregator.client_sampling:89-97), which stateful
        algorithms (SCAFFOLD/Ditto/FedDyn) mirror to re-derive their
        cohort.  dp_fedavg overrides this with SECRET rng-derived sampling:
        a public, run-independent cohort schedule voids the
        amplification-by-subsampling assumption its accountant relies on."""
        return sample_clients(round_idx, self.data.client_num,
                              self.cfg.client_num_per_round)

    def init_params(self, rng: Optional[jax.Array] = None):
        rng = rng if rng is not None else jax.random.key(self.cfg.seed)
        sample = jax.tree.map(lambda v: v[0, 0], {
            "x": self.data.train["x"], "y": self.data.train["y"],
            "mask": self.data.train["mask"]})
        return self.workload.init(rng, sample)

    # -- checkpoint hooks (overridden by stateful servers, e.g. FedOpt) ----
    def _extra_state(self):
        return {}

    def _extra_state_template(self, params):
        return {}

    def _load_extra_state(self, extra) -> None:
        pass

    def _ckpt_state(self, params, rng, round_idx):
        state = {"params": params, "rng": rng, "round": round_idx}
        extra = self._extra_state()
        if extra:
            state["extra"] = extra
        return state

    def _maybe_resume(self, checkpointer, params, rng):
        """Restore (params, rng, next round, server state) from the latest
        round checkpoint, if one exists (SURVEY.md §5.4)."""
        if checkpointer is None or checkpointer.latest_round() is None:
            return params, rng, 0
        template = {"params": params, "rng": rng, "round": 0}
        extra_t = self._extra_state_template(params)
        if extra_t:
            template["extra"] = extra_t
        try:
            state = checkpointer.restore(like=template)
        except ValueError:
            # the snapshot's extra-state layout differs from this run's
            # template (older snapshot, or a different server optimizer)
            # — restore untemplated and let _load_extra_state decide
            # whether that is back-compat (accept + warn) or a foreign
            # trajectory (named refusal)
            state = checkpointer.restore()
        if "extra" in state:
            self._load_extra_state(state["extra"])
        logger.info("resumed from round %d (%s)", state["round"],
                    checkpointer.ckpt_dir)
        return state["params"], state["rng"], int(state["round"]) + 1

    def run(self, params=None, rng: Optional[jax.Array] = None,
            checkpointer=None):
        cfg = self.cfg
        rng = rng if rng is not None else jax.random.key(cfg.seed)
        if params is None:
            rng, init_rng = jax.random.split(rng)
            params = self.workload.init(init_rng, jax.tree.map(
                lambda v: v[0, 0], {k: self.data.train[k]
                                    for k in ("x", "y", "mask")}))
        params, rng, start_round = self._maybe_resume(checkpointer, params, rng)

        from jax.sharding import PartitionSpec as P
        # multi-process pods: host data must enter the global-mesh jit as
        # global jax.Arrays (no-op single-process)
        params = stage_global(params, self.mesh)
        # the HBM-resident fast path only serves the BASE cohort step —
        # subclasses that replace cohort_step wholesale (FedNova, Robust
        # with defenses) must not be bypassed.  FedProx rides it via the
        # local_train seam; FedOpt via the _server_update hook.
        use_device_data = (self.mesh is None
                           and (self.cohort_step is self._base_cohort_step
                                or self._device_round_override is not None)
                           and self._stage_train_on_device())
        if (use_device_data and cfg.rounds_per_dispatch > 1
                and checkpointer is None and self._server_update is None
                and self.cohort_step is self._base_cohort_step):
            return self._run_scanned(params, rng, start_round)
        for round_idx in range(start_round, cfg.comm_round):
            t0 = time.time()
            ids = self._sample_round(round_idx)
            rng, round_rng = jax.random.split(rng)
            if use_device_data:
                m = cfg.client_num_per_round
                live = np.ones(m, np.float32)
                live[len(ids):] = 0.0
                padded_ids = np.zeros(m, np.int32)
                padded_ids[:len(ids)] = ids
                w_agg, _ = self._device_round(
                    params, self._train_dev, jax.numpy.asarray(padded_ids),
                    jax.numpy.asarray(live), round_rng)
            else:
                cohort = gather_cohort(self.data.train, ids,
                                       pad_to=cfg.client_num_per_round)
                cohort = stage_global(cohort, self.mesh, P("clients"))
                round_rng = stage_global(round_rng, self.mesh)
                w_agg, _ = self.cohort_step(params, cohort, round_rng)
            if self._server_update is not None:
                w_agg = self._server_update(params, w_agg)
            params = w_agg
            jax.block_until_ready(params)
            round_s = time.time() - t0

            if (round_idx % cfg.frequency_of_the_test == 0
                    or round_idx == cfg.comm_round - 1):
                stats = self.evaluate_global(params)
                stats.update(round=round_idx, round_s=round_s)
                logger.info("round %d: %s", round_idx, stats)
                self.history.append(stats)
                if self.sink is not None:
                    self.sink.log(stats, step=round_idx)
            if checkpointer is not None:
                checkpointer.maybe_save(
                    round_idx, self._ckpt_state(params, rng, round_idx),
                    last_round=round_idx == cfg.comm_round - 1)
        if checkpointer is not None:
            # async_save: the final background write must be durable (and
            # any write error surfaced) before the run reports success
            checkpointer.flush()
        return params

    def _run_scanned(self, params, rng, start_round):
        """Chunked fast path: K rounds per device dispatch (lax.scan inside
        one jit, data HBM-resident), chunk boundaries at eval rounds."""
        from fedml_tpu.parallel.cohort import make_scanned_rounds
        cfg = self.cfg
        m = cfg.client_num_per_round
        # one jit'd rounds_fn serves every chunk size (cache keys on shapes)
        rounds_fn = make_scanned_rounds(self._local_train, m,
                                        client_axis=cfg.client_axis)

        round_idx = start_round
        while round_idx < cfg.comm_round:
            # next boundary: the next round whose END needs an eval
            nxt = round_idx
            while not (nxt % cfg.frequency_of_the_test == 0
                       or nxt == cfg.comm_round - 1):
                nxt += 1
            K = min(nxt - round_idx + 1, cfg.rounds_per_dispatch)
            ids = np.zeros((K, m), np.int32)
            live = np.zeros((K, m), np.float32)
            for k in range(K):
                r_ids = self._sample_round(round_idx + k)
                ids[k, :len(r_ids)] = r_ids
                live[k, :len(r_ids)] = 1.0
            rng, chunk_rng = jax.random.split(rng)
            t0 = time.time()
            params, _ = rounds_fn(params, self._train_dev,
                                  jax.numpy.asarray(ids),
                                  jax.numpy.asarray(live), chunk_rng)
            jax.block_until_ready(params)
            chunk_s = time.time() - t0
            round_idx += K
            last = round_idx - 1
            if (last % cfg.frequency_of_the_test == 0
                    or last == cfg.comm_round - 1):
                stats = self.evaluate_global(params)
                stats.update(round=last, round_s=chunk_s / K)
                logger.info("round %d: %s", last, stats)
                self.history.append(stats)
                if self.sink is not None:
                    self.sink.log(stats, step=last)
        return params

    def _stage_train_on_device(self, budget_bytes: Optional[int] = None
                               ) -> bool:
        """Upload the stacked train set to HBM once (returns False when it
        exceeds the budget — 4 GiB default, FEDML_TPU_DEVICE_DATA_BYTES to
        override — falling back to per-round host gather)."""
        if self._train_dev is not None:
            return True
        import os
        budget = budget_bytes if budget_bytes is not None else int(
            os.environ.get("FEDML_TPU_DEVICE_DATA_BYTES", str(4 << 30)))
        nbytes = sum(np.asarray(v).nbytes for v in self.data.train.values())
        if nbytes > budget:
            logger.info("train set %.1f MB > device budget; using host "
                        "gather", nbytes / 1e6)
            return False
        if self._device_round is None:
            self._device_round = (self._device_round_override
                                  or make_device_round(
                                      self._local_train,
                                      self.cfg.client_num_per_round,
                                      client_axis=self.cfg.client_axis))
        self._train_dev = {k: jax.numpy.asarray(v)
                           for k, v in self.data.train.items()}
        return True

    def _fits_with_train(self, stacked) -> bool:
        """True when this split fits in the device-data budget ALONGSIDE
        the already-resident train split (same knob as
        _stage_train_on_device)."""
        import os
        budget = int(os.environ.get("FEDML_TPU_DEVICE_DATA_BYTES",
                                    str(4 << 30)))
        train_b = sum(np.asarray(v).nbytes
                      for v in self.data.train.values())
        split_b = sum(np.asarray(v).nbytes for v in stacked.values())
        return train_b + split_b <= budget

    def evaluate_global(self, params) -> Dict[str, float]:
        """Weighted train/test metrics over ALL clients' shards (parity with
        _local_test_on_all_clients, fedavg_api.py:118-171).  Corpora larger
        than ``eval_chunk_clients`` are swept in fixed-size client chunks
        (summed metric dicts are exact under chunking; zero-mask padding of
        the last chunk contributes nothing)."""
        from jax.sharding import PartitionSpec as P
        out: Dict[str, float] = {}
        for split, stacked in (("train", self.data.train), ("test", self.data.test)):
            if stacked is None:
                continue
            chunk = self.cfg.eval_chunk_clients
            n_clients = stacked["num_samples"].shape[0]
            if chunk and n_clients > chunk:
                from fedml_tpu.utils.metrics import stats_from_metrics
                m = self._eval_cohort_chunked(params, stacked, chunk)
                out.update(stats_from_metrics(m, prefix=f"{split}_"))
                continue
            # once the train set is device-resident, reuse it; cache the
            # test split too when train+test together stay inside the
            # device-data budget (else upload per eval and let it free)
            if split == "train" and self._train_dev is not None:
                batch = self._train_dev
            elif split == "test" and self._train_dev is not None:
                if self._test_dev is None and self._fits_with_train(stacked):
                    self._test_dev = {k: jax.numpy.asarray(v)
                                      for k, v in stacked.items()}
                batch = self._test_dev if self._test_dev is not None else {
                    k: jax.numpy.asarray(v) for k, v in stacked.items()}
            else:
                batch = {k: jax.numpy.asarray(v) for k, v in stacked.items()}
            if self.mesh is not None and jax.process_count() > 1:
                # cohort_eval pads to the device count internally, but global
                # staging must happen pre-jit, so pad here first
                from fedml_tpu.parallel.cohort import pad_clients
                batch = pad_clients(batch, self.mesh.shape["clients"])
                batch = stage_global(batch, self.mesh, P("clients"))
            from fedml_tpu.utils.metrics import stats_from_metrics
            m = self._eval_cohort(params, batch)
            out.update(stats_from_metrics(m, prefix=f"{split}_"))
        return out

    def _eval_cohort_chunked(self, params, stacked, chunk: int):
        """Sum the cohort-eval metric dict over [chunk]-client slices;
        each chunk rides the same `_eval_cohort` as the one-shot path,
        with multi-process chunks staged globally pre-jit."""
        from jax.sharding import PartitionSpec as P
        from fedml_tpu.parallel.cohort import pad_clients

        def run_chunk(part, lo):
            if self.mesh is not None and jax.process_count() > 1:
                part = pad_clients(part, self.mesh.shape["clients"])
                part = stage_global(part, self.mesh, P("clients"))
            return self._eval_cohort(params, part)

        return sweep_eval_chunks(stacked, chunk, run_chunk)
