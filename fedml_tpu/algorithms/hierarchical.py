"""Hierarchical FL — two-tier client -> group (edge) -> global averaging.

Parity with fedml_api/standalone/hierarchical_fl/:
* random client->group assignment (trainer.py:12-18, ``group_method ==
  'random'``);
* per global round: the plain seeded sampler picks clients, which are routed
  to their groups (trainer.py:32-41);
* each group runs ``group_comm_round`` FedAvg rounds among its sampled
  clients (group.py:24-46), then groups average weighted by their sampled
  clients' sample counts (trainer.py:56-62).

TPU mapping (SURVEY.md §2.5): group tier = ICI within a pod slice, global
tier = DCN across slices.  Single-chip, the WHOLE two-tier round is one jit:
group cohorts are padded to one static [G, M, ...] bucket, each group's
``group_comm_round`` FedAvg rounds run as a `lax.scan`, and the G groups run
simultaneously under `vmap` — groups are a batch axis, not a Python loop.
On a mesh the groups iterate host-side over the client-sharded cohort step
(each group already parallel over its clients' devices).
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Dict, List, Optional

import jax
import numpy as np

import jax.numpy as jnp

from fedml_tpu.algorithms.fedavg import FedAvg, FedAvgConfig
from fedml_tpu.core.pytree import tree_weighted_mean
from fedml_tpu.core.sampling import sample_clients
from fedml_tpu.data.stacking import gather_cohort
from fedml_tpu.parallel.cohort import train_cohort

logger = logging.getLogger(__name__)

# edge straggler timer self-message (continues the MsgType numbering of
# algorithms/cross_silo.py (1-6) and async_fl's MSG_RETASK_TICK (7))
MSG_EDGE_TIMEOUT = 8


def make_grouped_round(local_train, group_comm_round: int):
    """One jit for an entire hierarchical round: vmap over the group axis of
    a scanned multi-round FedAvg (group.py:24-46 per group, trainer.py:56-62
    across groups).

    ``grouped(params, cohorts, rng) -> new_params`` with cohort leaves
    [G, M, S, B, ...]; a group whose sampled-client weights are all zero
    (possible under random assignment) passes params through unchanged.
    """

    def group_run(params, cohort, rng):
        # guard the weights, not the mean: an all-padding (empty) group gets
        # uniform dummy weights so tree_weighted_mean stays finite (ints
        # included), then the result is discarded by the total>0 select
        total = jnp.sum(cohort["num_samples"].astype(jnp.float32))
        safe_w = jnp.where(total > 0, cohort["num_samples"],
                           jnp.ones_like(cohort["num_samples"]))

        def body(carry, _):
            p, r = carry
            r, rr = jax.random.split(r)
            stacked, _ = train_cohort(local_train, p, cohort, rr,
                                      client_axis="vmap")
            p_new = tree_weighted_mean(stacked, safe_w)
            # empty group: no clients -> model unchanged
            p = jax.tree.map(
                lambda new, old: jnp.where(total > 0, new, old), p_new, p)
            return (p, r), None

        (p, _), _ = jax.lax.scan(body, (params, rng), None,
                                 length=group_comm_round)
        return p, total

    @jax.jit
    def grouped(params, cohorts, rng):
        rngs = jax.vmap(lambda i: jax.random.fold_in(rng, i))(
            jnp.arange(cohorts["num_samples"].shape[0]))
        group_params, group_w = jax.vmap(
            group_run, in_axes=(None, 0, 0))(params, cohorts, rngs)
        return tree_weighted_mean(group_params, group_w)

    return grouped


@dataclasses.dataclass
class HierarchicalConfig(FedAvgConfig):
    group_num: int = 2
    group_comm_round: int = 2
    group_method: str = "random"


def make_two_level_round(local_train, group_comm_round: int, mesh):
    """The SURVEY §2.5 two-level mesh: a [groups, clients] device grid where
    each group's ``group_comm_round`` FedAvg rounds aggregate with `psum`
    over the ``clients`` axis (ICI within a slice) and the final global
    average is a weighted `psum` over the ``groups`` axis (DCN across
    slices).  One jit; same math and rng streams as `make_grouped_round`
    (parity-tested), so single-chip simulation and pod execution are
    interchangeable.

    ``two_level(params, cohorts, rng) -> new_params`` with cohort leaves
    [G, M, S, B, ...], G == mesh groups axis, M divisible by the clients
    axis.
    """
    from jax.sharding import PartitionSpec as P

    def per_device(params, cohort, rng):
        params = jax.lax.pcast(params, ("groups", "clients"),
                               to="varying")
        rng = jax.lax.pcast(rng, ("groups", "clients"), to="varying")
        g = jax.lax.axis_index("groups")
        c = jax.lax.axis_index("clients")
        local = jax.tree.map(lambda v: v[0], cohort)   # [M/D, ...] shard
        m_loc = local["num_samples"].shape[0]
        w = local["num_samples"].astype(jnp.float32)
        total_g = jax.lax.psum(jnp.sum(w), "clients")
        ratio = w / jnp.maximum(total_g, 1.0)
        r_g = jax.random.fold_in(rng, g)

        def body(carry, _):
            p, r = carry
            r, rr = jax.random.split(r)
            stacked, _ = train_cohort(local_train, p, local, rr,
                                      index_offset=c * m_loc,
                                      client_axis="vmap")
            # accumulate in f32 and cast back, matching tree_weighted_mean
            # (exact for int leaves, full precision for bf16 params)
            p_new = jax.tree.map(
                lambda x: jax.lax.psum(jnp.sum(
                    x.astype(jnp.float32)
                    * ratio.reshape((-1,) + (1,) * (x.ndim - 1)),
                    axis=0), "clients").astype(x.dtype), stacked)
            p = jax.tree.map(
                lambda new, old: jnp.where(total_g > 0, new, old), p_new, p)
            return (p, r), None

        (p_g, _), _ = jax.lax.scan(body, (params, r_g), None,
                                   length=group_comm_round)
        # global tier: sample-weighted mean of group models over DCN.
        # p_g is replicated across the clients axis (it came out of a
        # clients-psum), so reduce over BOTH axes and divide out the D
        # duplicate copies — this also lets shard_map statically prove the
        # P() (fully replicated) out_spec
        tot = jax.lax.psum(total_g, "groups")
        D = jax.lax.axis_size("clients")
        share = total_g / jnp.maximum(tot, 1.0) / D
        return jax.tree.map(
            lambda x: jax.lax.psum(x.astype(jnp.float32) * share,
                                   ("groups", "clients")).astype(x.dtype),
            p_g)

    sharded = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(P(), P("groups", "clients"), P()), out_specs=P())
    return jax.jit(sharded)


class HierarchicalFedAvg(FedAvg):
    def __init__(self, workload, data, config: HierarchicalConfig, mesh=None, sink=None):
        two_level = mesh is not None and "groups" in mesh.axis_names
        super().__init__(workload, data, config,
                         mesh=None if two_level else mesh, sink=sink)
        # staging target: multi-process pods need global jax.Arrays even on
        # the two-level path (self.mesh is None there by construction)
        self._stage_mesh = mesh
        cfg = config
        if cfg.group_method != "random":
            raise ValueError(f"unknown group_method {cfg.group_method!r}")
        if cfg.client_axis not in (None, "vmap"):
            # grouped/two-level rounds vmap inside their own bodies; a
            # silently-ignored "scan" request would mislabel the engine
            raise ValueError("client_axis is not wired into hierarchical "
                             "FL's grouped rounds; leave it None")
        rng = np.random.RandomState(cfg.seed)
        self.group_indexes = rng.randint(0, cfg.group_num, data.client_num)
        if two_level:
            # [groups, clients] device grid (make_two_level_mesh): group
            # aggregation over ICI, global over DCN — one jit per round
            if cfg.group_num != mesh.shape["groups"]:
                raise ValueError(
                    f"group_num={cfg.group_num} must equal the mesh groups "
                    f"axis ({mesh.shape['groups']})")
            if cfg.client_num_per_round % mesh.shape["clients"]:
                raise ValueError(
                    f"client_num_per_round={cfg.client_num_per_round} must "
                    f"be a multiple of the mesh clients axis "
                    f"({mesh.shape['clients']})")
            self._grouped_round = make_two_level_round(
                self._local_train, cfg.group_comm_round, mesh)
        else:
            # single-chip: all groups train simultaneously (vmap'd group
            # axis); 1-D client mesh falls back to the host group loop
            self._grouped_round = (None if mesh is not None else
                                   make_grouped_round(self._local_train,
                                                      cfg.group_comm_round))

    def _group_clients(self, ids: np.ndarray) -> Dict[int, List[int]]:
        groups: Dict[int, List[int]] = {}
        for cid in ids:
            groups.setdefault(int(self.group_indexes[cid]), []).append(int(cid))
        return groups

    def run(self, params=None, rng=None, checkpointer=None):
        cfg = self.cfg
        rng = rng if rng is not None else jax.random.key(cfg.seed)
        if params is None:
            rng, init_rng = jax.random.split(rng)
            params = self.workload.init(init_rng, jax.tree.map(
                lambda v: v[0, 0], {k: self.data.train[k]
                                    for k in ("x", "y", "mask")}))
        params, rng, start_round = self._maybe_resume(checkpointer, params, rng)

        from jax.sharding import PartitionSpec as P
        from fedml_tpu.parallel.mesh import stage_global
        params = stage_global(params, self._stage_mesh)
        for global_round in range(start_round, cfg.comm_round):
            ids = sample_clients(global_round, self.data.client_num,
                                 cfg.client_num_per_round)
            groups = self._group_clients(np.asarray(ids))
            if self._grouped_round is not None:
                # one jit: [G, M, ...] cohorts — groups vmapped (single
                # chip) or sharded over the [groups, clients] grid
                rng, rr = jax.random.split(rng)
                cohorts = [gather_cohort(self.data.train,
                                         groups.get(g, []),
                                         pad_to=cfg.client_num_per_round)
                           for g in range(cfg.group_num)]
                stacked = jax.tree.map(lambda *xs: jax.numpy.stack(xs),
                                       *cohorts)
                if self._stage_mesh is not None:
                    stacked = stage_global(stacked, self._stage_mesh,
                                           P("groups", "clients"))
                    rr = stage_global(rr, self._stage_mesh)
                params = self._grouped_round(params, stacked, rr)
            else:
                # same rng derivation as the vmapped path (fold_in by group
                # index, split per group round) so one seed yields one model
                # regardless of topology
                rng, rr = jax.random.split(rng)
                group_params, group_weights = [], []
                for gidx in sorted(groups):
                    gids = groups[gidx]
                    w_group = params
                    cohort = gather_cohort(self.data.train, gids,
                                           pad_to=cfg.client_num_per_round)
                    cohort = stage_global(cohort, self.mesh, P("clients"))
                    r_g = jax.random.fold_in(rr, gidx)
                    for group_round in range(cfg.group_comm_round):
                        r_g, rloc = jax.random.split(r_g)
                        rloc = stage_global(rloc, self.mesh)
                        w_group, _ = self.cohort_step(w_group, cohort, rloc)
                    group_params.append(w_group)
                    group_weights.append(
                        float(self.data.train["num_samples"][gids].sum()))
                params = tree_weighted_mean(group_params,
                                            jax.numpy.asarray(group_weights))

            if (global_round % cfg.frequency_of_the_test == 0
                    or global_round == cfg.comm_round - 1):
                stats = self.evaluate_global(params)
                stats["round"] = global_round
                self.history.append(stats)
                logger.info("global round %d: %s", global_round, stats)
                if self.sink is not None:
                    self.sink.log(stats, step=global_round)
            if checkpointer is not None:
                checkpointer.maybe_save(
                    global_round,
                    self._ckpt_state(params, rng, global_round),
                    last_round=global_round == cfg.comm_round - 1)
        if checkpointer is not None:
            checkpointer.flush()  # final async write durable before return
        return params


# ---------------------------------------------------------------------------
# live multi-level aggregator topology (edge aggregators -> root)
# ---------------------------------------------------------------------------

class EdgeAggregatorActor:
    """The live-transport promotion of this module's two-tier averaging:
    an intermediate aggregator that folds its silos' uploads LOCALLY and
    ships one pre-reduced update to the root (ROADMAP item 2's
    "hierarchical.py becomes a live multi-level aggregator topology").

    Wire choreography (all over the real transport, PR 5 encode-once
    frames end to end):

    * root ``S2C_INIT/SYNC`` -> edge: the edge re-broadcasts the global
      to its silos with ``send_many`` (one payload serialization per
      wave) and derives each silo's client assignment itself — the
      cohort sampler is deterministic in ``(round, client_num_in_total,
      cohort_total)``, so no assignment table ever rides the wire;
    * silo ``C2S_MODEL`` -> edge: screened by the edge's own
      `AdmissionPipeline` (PR 4 composes per-upload at the edge; the
      root's norm screen then sees the edge MEAN — screens compose
      across tiers), admitted uploads fold into the edge's
      `StreamingAggregator` at arrival (O(model) standing state);
    * edge ``C2S_MODEL`` -> root: ONE frame carrying the pre-reduced
      ``(sum / weight, weight, count)`` — the weighted mean as
      ``model_params``, the folded weight total as ``num_samples``, the
      fold count as ``edge_count`` (diagnostic-only wire field: the
      root aggregates by ``num_samples``).  ``mean(edge means, edge weights)
      == mean(all uploads, all weights)`` exactly, so the ROOT is an
      unmodified `FedAvgServerActor` whose "silos" are the edges: its
      straggler policies, admission screen, trust tracker, flight
      recorder, and both agg modes all apply per edge unchanged.  An
      edge with zero admissible uploads stays silent and the root's
      drop policy closes over it like any straggler (the chaos-dropped
      edge case, pinned by test).

    The downstream protocol equals the upstream one, so edges nest: an
    edge whose "silos" are themselves edges is a deeper tree with no new
    code.  ``silos`` maps transport node id -> 1-based GLOBAL cohort
    slot (the flat deployment's silo index, which seeds each silo's rng
    stream and client assignment — a silo trains identically under any
    topology).

    ``timeout_s``: edge-local straggler bound — after it, the edge
    flushes whatever folded (>= 1 upload) instead of wedging the root
    barrier on one lost silo upload.
    """

    def __init__(self, node_id: int, transport, silos: Dict[int, int],
                 cohort_total: int, client_num_in_total: int,
                 stream_agg, admission=None, root_id: int = 0,
                 timeout_s: Optional[float] = None, health=None,
                 secagg=None, journal=None, faultline=None):
        """``health``: a `fedml_tpu.obs.health.HealthAccumulator`
        (statistics-only — ``alarms=False``, no ledger: the root owns
        verdicts); when set, the edge folds its silos' learning-health
        stats at arrival and ships the compact per-round rollup inside
        its existing edge frame (`Message.ARG_HEALTH`) — the tree stays
        one-frame-per-round and the root renders a per-edge health
        table.

        ``secagg``: a `fedml_tpu.secure.protocol.SecAggServer` scoped to
        THIS edge's block (``--secagg grouped`` — TurboAggregate's
        grouped scheme on the live tree): the edge runs the whole
        secure-aggregation choreography for its silos — advert relay,
        roster, ring fold of masked uploads, unmask at flush — and ships
        the recovered plaintext PARTIAL MEAN to the root in the existing
        one-frame-per-round format, so the root stays an UNMODIFIED
        `FedAvgServerActor` and mask-agreement traffic drops from
        O(N²) to O(N²/E).  Mutually exclusive with ``stream_agg``.

        ``journal``: a `fedml_tpu.utils.journal.RoundJournal` scoped to
        THIS edge (its own directory) — the edge twin of the servers'
        mid-round crash consistency.  The plaintext fold snapshots
        durably (reference INCLUDED: a respawned edge has no live root
        sync to re-learn the round global from), so `resume()` on a
        rebuilt edge restores the fold mid-round and re-syncs only the
        silos whose uploads were not durable.  Masked (secagg) edge
        rounds journal abort-only: a respawned edge gives the round up
        and the root's straggler policy closes over it.

        ``faultline``: a `fedml_tpu.robust.faultline.Faultline` — the
        seeded process-kill injector (test/soak only)."""
        from fedml_tpu.comm.actors import ClientManager, SelfMessageTimer
        from fedml_tpu.obs import telemetry

        if (secagg is None) == (stream_agg is None):
            raise ValueError("EdgeAggregatorActor needs exactly one of "
                             "stream_agg (plaintext fold) or secagg "
                             "(masked ring fold)")

        # composition over inheritance for the manager plumbing: the
        # actor IS a ClientManager to the root and a server to its silos
        class _Mgr(ClientManager):
            def register_handlers(mgr) -> None:  # noqa: N805
                from fedml_tpu.algorithms.cross_silo import MsgType
                mgr.register_handler(MsgType.S2C_INIT, self._on_sync)
                mgr.register_handler(MsgType.S2C_SYNC, self._on_sync)
                mgr.register_handler(MsgType.C2S_MODEL, self._on_upload)
                mgr.register_handler(MsgType.C2S_HEARTBEAT, lambda m: None)
                mgr.register_handler(MSG_EDGE_TIMEOUT, self._on_timeout)
                mgr.register_handler(MsgType.S2C_FINISH, self._on_finish)
                if self.secagg is not None:
                    from fedml_tpu.secure.protocol import (
                        MSG_SECAGG_ADVERT, MSG_SECAGG_SHARES)
                    mgr.register_handler(MSG_SECAGG_ADVERT,
                                         self._on_secagg_advert)
                    mgr.register_handler(MSG_SECAGG_SHARES,
                                         self._on_secagg_shares)

        self.secagg = secagg
        self.journal = journal
        self.faultline = faultline
        self._mgr = _Mgr(node_id, transport)
        self.node_id = node_id
        self.silos = dict(silos)
        self.cohort_total = cohort_total
        self.client_num_in_total = client_num_in_total
        self.stream_agg = stream_agg
        self.admission = admission
        self.health = health
        self.root_id = root_id
        self.timeout_s = timeout_s
        self.round_idx: Optional[int] = None
        self._round_params = None
        self._received: set = set()
        self._timer = SelfMessageTimer()
        self._flushed = False
        self._secagg_stage: Optional[str] = None
        self._c_flush = telemetry.get_registry().counter(
            "fedml_stream_edge_flush_total")

    # -- lifecycle -----------------------------------------------------------
    def register_handlers(self) -> None:
        self._mgr.register_handlers()

    def run(self) -> None:
        self._mgr.run()

    def finish(self) -> None:
        self._timer.cancel(join=True)
        self._mgr.finish()

    @property
    def transport(self):
        return self._mgr.transport

    def resume(self) -> bool:
        """Mid-round recovery for a RESPAWNED edge (the root never
        re-syncs an edge it believes alive): restore the journal's open
        round — the snapshot carries the round reference, the fold
        state, and the durable fold list — re-sync only the silos whose
        uploads were not durable, and flush immediately when everything
        already folded.  Non-resumable rounds (masked, reservoir, no
        snapshot) are given up: the edge stays silent and the root's
        straggler policy closes over it like any dropped silo.  Returns
        True when a mid-round recovery engaged."""
        from fedml_tpu.comm.message import Message
        if self.journal is None:
            return False
        rec = self.journal.recover()
        if rec is None:
            return False
        if (not rec.resumable or rec.state is None or not rec.folded
                or rec.state.get("reference") is None):
            logger.warning(
                "edge %d: round %d crashed mid-flight without a "
                "resumable snapshot (mode=%s); giving the round up — "
                "the root's straggler policy closes over this edge",
                self.node_id, rec.round_idx, rec.mode)
            self.journal.abandon(rec.round_idx, "not resumable on edge")
            return False
        from fedml_tpu.algorithms.cross_silo import MsgType
        self.stream_agg.load_state_dict(rec.state)
        self.round_idx = rec.round_idx
        self._round_params = jax.tree.map(np.asarray,
                                          self.stream_agg.reference)
        self._flushed = False
        self._received = {int(s) for s, _, _ in rec.folded}
        # re-arms the journal's round state (fold prefix included) so
        # the resumed block keeps snapshotting on its cadence
        self.journal.note_resume(rec.round_idx, rec.folded,
                                 global_crc=rec.global_crc)
        if self.health is not None:
            # health is soft state: the recovery round reopens with the
            # fairness denominator intact; folded silos' payload stats
            # are gone with the process (advisory, never load-bearing)
            self.health.round_start(rec.round_idx, self._round_params,
                                    expected=sorted(self.silos))
        ids = sample_clients(rec.round_idx, self.client_num_in_total,
                             self.cohort_total)
        per_silo = {
            silo: {Message.ARG_CLIENT_INDEX: int(ids[g - 1])}
            for silo, g in sorted(self.silos.items())
            if g - 1 < len(ids) and silo not in self._received}
        logger.warning("edge %d: resuming round %d mid-round — %d fold(s) "
                       "restored, re-syncing silos %s", self.node_id,
                       rec.round_idx, len(self._received),
                       sorted(per_silo))
        if per_silo:
            self._mgr.send_many(
                MsgType.S2C_SYNC, sorted(per_silo),
                shared_params={
                    Message.ARG_MODEL_PARAMS: self._round_params,
                    Message.ARG_ROUND: rec.round_idx},
                per_receiver_params=per_silo)
            self._arm_timer()
        if self._received >= set(self.silos):
            self._flush()
        return True

    # -- root-facing side ----------------------------------------------------
    def _on_finish(self, msg) -> None:
        from fedml_tpu.algorithms.cross_silo import MsgType
        for silo in sorted(self.silos):
            self._mgr.send(MsgType.S2C_FINISH, silo)
        self.finish()

    def _on_sync(self, msg) -> None:
        from fedml_tpu.comm.message import Message
        round_idx = msg.get(Message.ARG_ROUND)
        params = msg.get(Message.ARG_MODEL_PARAMS)
        self.round_idx = round_idx
        self._received.clear()
        self._flushed = False
        self._secagg_stage = None
        # the round's reference global, kept for the admission screen —
        # the edge's own handle, not a reach into stream_agg internals
        self._round_params = params
        if self.journal is not None:
            from fedml_tpu.utils.journal import tree_crc
            self.journal.round_start(
                round_idx,
                mode=("secagg" if self.secagg is not None
                      else f"stream_{self.stream_agg.method}"),
                resumable=(self.secagg is None
                           and self.stream_agg.method == "mean"),
                global_crc=tree_crc(params),
                expected=sorted(self.silos))
        shared_extra = {}
        if self.secagg is not None:
            # the edge IS the secagg server for its block: the re-
            # broadcast carries the block's masking parameters, so the
            # silos of a grouped deployment mask exactly as flat ones do
            self.secagg.round_start(round_idx, sorted(self.silos))
            self._secagg_stage = "agreement"
            shared_extra[Message.ARG_SECAGG] = self.secagg.sync_info()
        else:
            self.stream_agg.reset(params)
        if self.health is not None:
            self.health.round_start(round_idx, params,
                                    expected=sorted(self.silos))
        # the deterministic sampler replays the FLAT deployment's
        # round-cohort assignment, so silo slot g trains client ids[g-1]
        # under any topology (parity with FedAvgServerActor._broadcast)
        ids = sample_clients(round_idx, self.client_num_in_total,
                             self.cohort_total)
        per_silo = {
            silo: {Message.ARG_CLIENT_INDEX: int(ids[g - 1])}
            for silo, g in sorted(self.silos.items()) if g - 1 < len(ids)}
        self._mgr.send_many(
            msg.type, sorted(per_silo),
            shared_params={Message.ARG_MODEL_PARAMS: params,
                           Message.ARG_ROUND: round_idx, **shared_extra},
            per_receiver_params=per_silo)
        self._arm_timer()

    # -- silo-facing side ----------------------------------------------------
    def _arm_timer(self) -> None:
        if self.timeout_s is None:
            return
        round_at_arm = self.round_idx
        from fedml_tpu.comm.message import Message
        self._timer.arm(
            self.timeout_s,
            lambda: self._mgr.send(MSG_EDGE_TIMEOUT, self.node_id,
                                   **{Message.ARG_ROUND: round_at_arm}))

    def _on_timeout(self, msg) -> None:
        from fedml_tpu.comm.message import Message
        if msg.get(Message.ARG_ROUND) != self.round_idx or self._flushed:
            return
        if self._secagg_stage == "agreement":
            from fedml_tpu.secure.protocol import SecAggError
            advertised = sorted(self.secagg.advertised())
            logger.warning("edge %d round %s: fixing the masking roster on "
                           "the %d silo(s) that advertised", self.node_id,
                           self.round_idx, len(advertised))
            try:
                self._send_rosters(subset=advertised)
            except SecAggError as e:
                self._give_up(f"roster below the share threshold ({e})")
            return
        if self._secagg_stage == "unmask":
            if self.secagg.can_finalize():
                self._finalize_secagg()
            else:
                self._give_up("below the unmask share threshold")
            return
        missing = sorted(set(self.silos) - self._received)
        logger.warning("edge %d round %s: silos %s missing after %.1fs; "
                    "flushing the partial fold", self.node_id,
                    self.round_idx, missing, self.timeout_s)
        self._flush()

    # -- secure aggregation (grouped masking, secure/protocol.py) ------------
    def _on_secagg_advert(self, msg) -> None:
        from fedml_tpu.comm.message import Message
        if msg.sender_id not in self.silos \
                or msg.get(Message.ARG_ROUND) != self.round_idx \
                or self._secagg_stage != "agreement":
            return
        if self.secagg.note_advert(msg.sender_id,
                                   msg.get(Message.ARG_SECAGG)):
            from fedml_tpu.secure.protocol import SecAggError
            try:
                self._send_rosters()
            except SecAggError as e:  # unreachable with a full group
                self._give_up(str(e))

    def _send_rosters(self, subset=None) -> None:
        from fedml_tpu.comm.message import Message
        from fedml_tpu.secure.protocol import MSG_SECAGG_ROSTER
        rosters = self.secagg.flush_roster(subset)  # raises below threshold
        self._secagg_stage = "upload"
        per = {silo: {Message.ARG_SECAGG: payload}
               for silo, payload in rosters.items()}
        self._mgr.send_many(MSG_SECAGG_ROSTER, sorted(per),
                            shared_params={Message.ARG_ROUND: self.round_idx},
                            per_receiver_params=per)
        self._arm_timer()

    def _begin_unmask(self) -> None:
        from fedml_tpu.comm.message import Message
        from fedml_tpu.secure.protocol import MSG_SECAGG_UNMASK
        self._secagg_stage = "unmask"
        survivors, dead = self.secagg.unmask_request()
        if dead:
            logger.warning("edge %d round %s: reconstructing dead silo(s) "
                           "%s from surviving shares", self.node_id,
                           self.round_idx, dead)
        self._mgr.send_many(
            MSG_SECAGG_UNMASK, survivors,
            shared_params={Message.ARG_ROUND: self.round_idx,
                           Message.ARG_SECAGG: {"survivors": survivors,
                                                "dead": dead}})
        self._arm_timer()

    def _on_secagg_shares(self, msg) -> None:
        from fedml_tpu.comm.message import Message
        if msg.get(Message.ARG_ROUND) != self.round_idx \
                or self._secagg_stage != "unmask":
            return
        if self.secagg.note_reveal(msg.sender_id,
                                   msg.get(Message.ARG_SECAGG)):
            self._finalize_secagg()

    def _finalize_secagg(self) -> None:
        """Unmask the block's ring sum and ship the plaintext partial
        mean to the root — the SAME one-frame-per-round format, so the
        root never knows its 'silo' spoke a masked protocol downstream."""
        from fedml_tpu.secure.protocol import SecAggError
        if self.faultline is not None:
            self.faultline.maybe_crash("mid_unmask",
                                       round_idx=self.round_idx)
        self._secagg_stage = None
        self._timer.cancel()
        try:
            mean, _den = self.secagg.finalize(reference=self._round_params)
        except SecAggError as e:
            self._give_up(f"unmask failed: {e}")
            return
        if mean is None:  # the post-unmask sum screen fired
            self._give_up("recovered sum rejected by the norm screen")
            return
        self._ship(mean, self.secagg.weight_total, self.secagg.count)

    def _give_up(self, why: str) -> None:
        """An unrecoverable masked round: stay SILENT (the root's
        straggler policy closes over this edge like any dropped silo) —
        a partially-unmasked sum must never ship."""
        logger.warning("edge %d round %s: giving up the masked round (%s); "
                       "not reporting", self.node_id, self.round_idx, why)
        self._secagg_stage = None
        self._flushed = True
        self._timer.cancel()
        if self.journal is not None:
            # the round is OVER for this edge (lost, global untouched):
            # a respawn must not try to resume it
            self.journal.abandon(self.round_idx, why)
            self.journal.round_end(self.round_idx)
        if self.health is not None:
            self.health.round_end(self.round_idx)

    def _on_upload(self, msg) -> None:
        from fedml_tpu.comm.message import Message
        if msg.sender_id not in self.silos:
            logger.warning("edge %d: upload from foreign silo %d dropped",
                        self.node_id, msg.sender_id)
            return
        upload_round = msg.get(Message.ARG_ROUND)
        if upload_round != self.round_idx or self._flushed:
            logger.warning("edge %d: discarding round-%s upload from silo %d "
                        "(current round %s%s)", self.node_id, upload_round,
                        msg.sender_id, self.round_idx,
                        ", already flushed" if self._flushed else "")
            return
        if msg.sender_id in self._received:
            logger.info("edge %d: ignoring duplicate round-%s upload from "
                     "silo %d", self.node_id, upload_round, msg.sender_id)
            return
        self._received.add(msg.sender_id)
        upload = msg.get(Message.ARG_MODEL_PARAMS)
        num_samples = msg.get(Message.ARG_NUM_SAMPLES)
        upload_norm = None
        if self.admission is not None:
            verdict = self.admission.admit(
                msg.sender_id, upload, num_samples,
                self._round_params, self.round_idx)
            if not verdict.ok:
                logger.warning("edge %d round %s: rejecting upload from silo "
                            "%d (reason=%s)", self.node_id, self.round_idx,
                            msg.sender_id, verdict.reason)
                if self.health is not None:
                    self.health.observe_rejected(msg.sender_id,
                                                 verdict.reason)
                num_samples = None
            else:
                num_samples = verdict.num_samples
                upload_norm = verdict.norm
        if num_samples is not None:
            if self.health is not None:
                # health folds before the aggregation fold consumes the
                # upload — the edge's block-level stats ride to the root
                # in this round's frame (payload stats suppressed by name
                # under masking)
                self.health.observe_admitted(msg.sender_id, upload,
                                             float(num_samples),
                                             norm=upload_norm)
            if self.faultline is not None:
                self.faultline.maybe_crash("post_admission_pre_fold",
                                           round_idx=self.round_idx,
                                           silo=msg.sender_id)
            if self.secagg is not None:
                from fedml_tpu.secure.protocol import SecAggError
                if self._secagg_stage != "upload":
                    logger.warning("edge %d: masked upload from silo %d "
                                   "outside the upload stage; dropped",
                                   self.node_id, msg.sender_id)
                else:
                    try:
                        self.secagg.fold(msg.sender_id, upload,
                                         float(num_samples))
                    except SecAggError as e:
                        logger.warning("edge %d: rejecting masked upload "
                                       "from silo %d (%s)", self.node_id,
                                       msg.sender_id, e)
                    else:
                        if self.journal is not None:
                            # metadata only: masked edge rounds are
                            # journalled abort-only (never snapshotted)
                            self.journal.note_accept(self.round_idx,
                                                     msg.sender_id,
                                                     float(num_samples))
            else:
                self.stream_agg.fold(upload, float(num_samples))
                if self.journal is not None:
                    # the reference rides INSIDE the edge snapshot: a
                    # respawned edge has no live root sync to re-learn
                    # the round global from
                    self.journal.note_accept(
                        self.round_idx, msg.sender_id, float(num_samples),
                        state_fn=(
                            (lambda: self.stream_agg.state_dict(
                                include_reference=True))
                            if self.stream_agg.method == "mean" else None))
        elif self.journal is not None:
            self.journal.note_accept(self.round_idx, msg.sender_id, 0.0,
                                     folded=False, reason="rejected")
        if self.faultline is not None:
            self.faultline.maybe_crash("post_fold_pre_ack",
                                       round_idx=self.round_idx,
                                       silo=msg.sender_id)
        if self.secagg is not None:
            # the masked barrier closes over the ROSTER (silos that never
            # advertised can never upload) by REPORTS, not folds — a
            # reported-but-rejected upload must close the barrier exactly
            # as on the flat root, or one inadmissible frame stalls the
            # block to full timeout (and wedges it forever under the
            # wait policy's timeout_s=None)
            if self._secagg_stage == "upload" \
                    and self._received >= \
                    set(self.secagg.roster_members()):
                self._flush()
            return
        if self._received >= set(self.silos):
            self._flush()

    def _flush(self) -> None:
        """Close the block's upload phase.  Plaintext: ship the fold's
        pre-reduced mean immediately.  Masked: the fold is still
        ciphertext — begin the unmask phase instead (the frame ships
        from `_finalize_secagg` once the share reveals land)."""
        if self.faultline is not None:
            self.faultline.maybe_crash("barrier_close",
                                       round_idx=self.round_idx)
        self._timer.cancel()
        if self.secagg is not None:
            if self.secagg.count == 0:
                self._give_up("no admissible masked uploads")
                return
            self._begin_unmask()
            return
        self._flushed = True
        if self.stream_agg.count == 0:
            # nothing admissible: stay silent; the root's straggler
            # policy closes over this edge like any dropped silo
            logger.warning("edge %d round %s: no admissible uploads; not "
                        "reporting", self.node_id, self.round_idx)
            if self.journal is not None:
                self.journal.round_end(self.round_idx)
            if self.health is not None:
                # still close the health round: the per-silo fairness
                # ledger must record who never showed
                self.health.round_end(self.round_idx)
            return
        mean = jax.tree.map(np.asarray,
                            self.stream_agg.finalize(self.round_idx))
        self._ship(mean, self.stream_agg.weight_total, self.stream_agg.count)

    def _ship(self, mean, weight_total: float, count: int) -> None:
        """One pre-reduced frame to the root: the block mean, its weight
        total, and the fold count — identical format for the plaintext
        and masked paths."""
        from fedml_tpu.algorithms.cross_silo import MsgType
        from fedml_tpu.comm.message import Message
        self._flushed = True
        self._c_flush.inc()
        extra = {}
        if self.health is not None:
            # close on the edge's own mean: its global_delta_norm says
            # how far THIS block moved off the broadcast global
            self.health.round_end(self.round_idx, new_global=mean)
            summary = self.health.round_summary()
            if summary is not None:
                extra[Message.ARG_HEALTH] = summary
        self._mgr.send(
            MsgType.C2S_MODEL, self.root_id,
            **{Message.ARG_MODEL_PARAMS: mean,
               Message.ARG_NUM_SAMPLES: float(weight_total),
               Message.ARG_ROUND: self.round_idx,
               Message.ARG_EDGE_COUNT: int(count),
               **extra})
        if self.journal is not None:
            # round_end AFTER the send: a crash between the two makes
            # the resumed edge re-finalize and re-ship — the root's
            # duplicate-report guard discards the second frame, so the
            # contract is at-least-once with root-side dedupe (the
            # reverse order would silently LOSE the block on a crash
            # between round_end and the send)
            self.journal.round_end(self.round_idx)
