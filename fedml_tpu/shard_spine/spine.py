"""The shard spine bundle: plan + sharded fold + sharded admission, and
the wire helpers both actor ends speak.

Server side, `ShardSpine` is what `--model_shards S` hands
`FedAvgServerActor` (``shard_wire=``): it owns the per-round broadcast
slices (one encode-once `SharedPayload` fan-out PER SHARD — S payload
serializations per round, never one per receiver), the per-silo upload
assembly + admission, and the plan identity the round checkpoint
records (``extra_state`` hook) so a resume re-derives — and verifies —
the identical layout.

Silo side, `SiloShardAssembler` banks a round's inbound shard slices
until all S arrived (any order), joins them into the params tree the
train fn consumes, and splits the trained tree back into upload slices
— all driven by the plan spec riding shard 0's sync frame, so a silo
needs ZERO shard configuration (the secagg sync-frame discipline).
"""

from __future__ import annotations

import logging
from typing import Dict, List, Optional

import numpy as np

from fedml_tpu.shard_spine.admission import ShardAdmission
from fedml_tpu.shard_spine.agg import ShardedStreamingAggregator
from fedml_tpu.shard_spine.plan import (ShardPlan, SiloShardCodec,
                                        build_shard_plan)

log = logging.getLogger(__name__)


class ShardSpine:
    """Everything the sharded round needs, built once per federation."""

    def __init__(self, plan: ShardPlan, agg: ShardedStreamingAggregator,
                 admission: Optional[ShardAdmission]):
        self.plan = plan
        self.agg = agg
        self.admission = admission
        self._spec = plan.spec()

    @property
    def num_shards(self) -> int:
        return self.plan.num_shards

    # -- server round lifecycle ----------------------------------------------
    def round_start(self, host_params) -> None:
        if self.admission is not None:
            self.admission.round_start(host_params)

    def round_end(self) -> None:
        if self.admission is not None:
            self.admission.round_end()

    def broadcast_slices(self, host_params) -> List[dict]:
        """The round's per-shard broadcast payloads (host views — each
        becomes ONE `SharedPayload` for the whole cohort)."""
        import jax
        leaves = [np.asarray(x) for x in jax.tree.leaves(host_params)]
        return self.plan.split_leaves(leaves)

    def spec(self) -> dict:
        """The plan descriptor shard 0's sync frame ships (static
        across rounds — silos rebuild split/join from it alone)."""
        return self._spec

    def join(self, slices: List[dict]):
        """Slices -> full host tree (the health observatory's view of
        an admitted upload)."""
        import jax
        leaves = self.plan.join_slices(slices)
        return jax.tree.unflatten(self.agg._treedef, leaves)

    # -- checkpoint identity (extra_state hook) ------------------------------
    def checkpoint_state(self) -> Dict[str, np.ndarray]:
        """Fixed-shape record of the layout for the round checkpoint:
        a resume re-derives the plan from the same (template, S,
        threshold) and VERIFIES the fingerprint matches — restoring
        sharded state under a silently different layout is the one
        mistake this subsystem must make impossible."""
        return {"num_shards": np.asarray(self.plan.num_shards, np.int64),
                "plan_fp": np.asarray(self.plan.fingerprint(), np.int64)}

    def restore_checkpoint_state(self, state) -> None:
        want_s = int(np.asarray(state["num_shards"]))
        want_fp = int(np.asarray(state["plan_fp"]))
        if want_s != self.plan.num_shards:
            raise ValueError(
                f"checkpoint was written under --model_shards {want_s} "
                f"but this run uses {self.plan.num_shards}; resume with "
                f"the original shard count (the layout is part of the "
                f"checkpointed state)")
        if want_fp != self.plan.fingerprint():
            raise ValueError(
                "checkpoint records a different shard-plan fingerprint "
                "than this run re-derived (the model or split threshold "
                "changed); refusing to resume under a mismatched layout")

    # the journal round-mode tag: recovery refuses a journal written by
    # a different aggregation configuration (plain <-> sharded, or a
    # different S) instead of unflattening foreign fold state
    def journal_mode(self) -> str:
        return f"shard_mean[S={self.plan.num_shards}]"


def build_shard_spine(template, *, num_shards: int,
                      norm_clip: float = 0.0, noise_std: float = 0.0,
                      seed: int = 0, fused: str = "auto",
                      admission_on: bool = True,
                      max_num_samples: float = 1e6, norm_k: float = 6.0,
                      norm_window: int = 64, norm_min_history: int = 8,
                      trust=None, min_split_elems: int = 1024,
                      mesh="auto", sentry=None, device=None) -> ShardSpine:
    """Build the spine from the live template.

    ``fused``: ``"on"`` wires the Pallas finalize unconditionally
    (interpreted on the CPU — the parity/proof mode); ``"auto"`` wires it
    on a TPU, where Mosaic compiles it, and keeps the XLA compose on the
    CPU (an interpreted kernel is a correctness tool, not a speedup);
    ``"off"`` keeps the XLA compose everywhere.

    ``mesh="auto"``: build a ``[1, S]`` model mesh when the host has at
    least S devices (each shard's fold state then lives on its own
    device); pass None to force placement-free, or a mesh to reuse one.
    """
    if fused not in ("auto", "on", "off"):
        raise ValueError(f"fused must be auto|on|off, got {fused!r}")
    import jax
    use_fused = fused == "on" or (fused == "auto"
                                  and jax.default_backend() == "tpu")
    if mesh == "auto":
        from fedml_tpu.parallel.mesh import make_model_mesh
        mesh = make_model_mesh(num_shards)
        if mesh is None and num_shards > 1:
            log.info("--model_shards %d on a %d-device host: shards "
                     "share the default device (same math; per-device "
                     "memory split needs >= %d devices)",
                     num_shards, len(jax.devices()), num_shards)
    plan = build_shard_plan(template, num_shards,
                            min_split_elems=min_split_elems)
    agg = ShardedStreamingAggregator(
        plan, template, norm_clip=norm_clip, noise_std=noise_std,
        seed=seed, fused=use_fused, mesh=mesh, sentry=sentry,
        device=device)
    admission = None
    if admission_on:
        admission = ShardAdmission(
            plan, template, max_num_samples=max_num_samples,
            norm_k=norm_k, norm_window=norm_window,
            norm_min_history=norm_min_history, trust=trust)
    return ShardSpine(plan, agg, admission)


class SiloShardAssembler:
    """Client-side shard choreography: bank sync slices per round until
    complete, join for training, split the trained tree for upload."""

    def __init__(self):
        self._codec: Optional[SiloShardCodec] = None
        self._round: Optional[int] = None
        self._slices: Dict[int, dict] = {}
        self._meta: Dict[str, object] = {}

    def offer(self, round_idx, shard, num_shards, slice_payload,
              spec: Optional[dict], meta: Optional[dict] = None) -> bool:
        """Bank one sync slice; returns True when the round's model is
        complete.  ``spec`` rides shard 0's frame; ``meta`` (client_idx,
        EF ack, ...) is banked from whichever frame carries it."""
        if spec is not None:
            if self._codec is None \
                    or self._codec.fingerprint != ShardPlan.from_spec(
                        spec).fingerprint():
                self._codec = SiloShardCodec(spec)
        if self._codec is None:
            log.warning("shard slice arrived before any plan spec; "
                        "dropping it (shard 0's frame carries the spec)")
            return False
        if num_shards is not None \
                and int(num_shards) != self._codec.num_shards:
            log.warning("shard slice claims %s shards but the plan has "
                        "%d; dropping it", num_shards,
                        self._codec.num_shards)
            return False
        if round_idx != self._round:
            if self._round is not None and round_idx is not None \
                    and round_idx < self._round:
                # a STALE frame (chaos delay/dup of an older round) must
                # not destroy the current round's partial assembly —
                # only a NEWER round supersedes it
                log.info("dropping stale round-%s shard slice (current "
                         "round %s)", round_idx, self._round)
                return False
            self._round = round_idx
            self._slices = {}
            self._meta = {}
        if meta:
            self._meta.update(meta)
        try:
            shard = int(shard)
        except (TypeError, ValueError):
            shard = -1
        if not 0 <= shard < self._codec.num_shards:
            # a mislabeled frame banked out of range would make the
            # completion count lie and take() KeyError mid-handler —
            # drop it like the server-side ShardAdmission does
            log.warning("dropping shard slice with out-of-range index "
                        "%s (plan has %d shards)", shard,
                        self._codec.num_shards)
            return False
        self._slices[shard] = slice_payload
        return len(self._slices) == self._codec.num_shards

    def take(self):
        """The completed round's ``(params_tree, meta)``; clears the
        bank."""
        slices = [self._slices[s]
                  for s in range(self._codec.num_shards)]
        params = self._codec.join(slices)
        meta = dict(self._meta)
        self._slices = {}
        self._meta = {}
        return params, meta

    def split_upload(self, new_params) -> List[dict]:
        if self._codec is None:
            raise RuntimeError("split_upload before any sync: no plan "
                               "spec has arrived")
        host = _as_host(new_params)
        return self._codec.split(host)

    @property
    def num_shards(self) -> Optional[int]:
        return None if self._codec is None else self._codec.num_shards


def _as_host(tree):
    import jax
    return jax.tree.map(np.asarray, tree)
