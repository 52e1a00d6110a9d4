"""Pipeline parallelism (pp): transformer blocks sharded over a
``stages`` mesh axis, GPipe-style microbatching via shard_map + ppermute.

The reference's only pipeline notion is SplitNN's two-party activation
exchange (fedml_api/standalone/split_nn); this module is the general
S-stage form for models too deep for one chip: each device holds L/S
consecutive blocks, microbatches stream through the stages, and the
activation hand-off between stages is a `lax.ppermute` hop riding ICI.
The whole schedule — fill, steady state, drain — is ONE `lax.scan` inside
ONE shard_map program, so XLA sees static shapes and the backward pass
falls out of jax autodiff (the transpose of ppermute is the reverse
permute, so gradients stream backward through the stages automatically —
no hand-written 1F1B needed for correctness).

Layout contract: block parameters carry an explicit leading layer axis
``[L, ...]`` (built by vmapped init), reshaped to ``[S, L/S, ...]`` and
placed with `P("stages")` — placement-as-parallelism, like tp
(mesh.tp_shard_params) and ep (expert.ep_shard_params).

Bubble accounting: a (M + S - 1)-step schedule does M steps of useful
work per stage — efficiency M/(M+S-1); pick n_micro >= n_stages for
>=50% (classic GPipe guidance).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedml_tpu.models.transformer import CausalSelfAttention
from fedml_tpu.trainer.workload import Workload, make_nwp_loss_metrics


def make_stage_mesh(n_stages: int,
                    devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < n_stages:
        raise ValueError(f"need {n_stages} devices for the stages axis, "
                         f"have {len(devices)}")
    return Mesh(np.asarray(devices[:n_stages]), ("stages",))


class TransformerBlock(nn.Module):
    """One pre-LN block (LN→MHA→residual, LN→FFN→residual) — the
    repeating unit the pipeline distributes.  Matches the DENSE inline
    blocks of models.transformer.TransformerLM (attention is the shared
    CausalSelfAttention module; only the LN/residual wiring is repeated
    here — mirror any change to that wiring in both places).

    ``moe_experts > 0`` swaps the dense MLP for the Switch FFN
    (models/moe.py) — the ep × pp composition.  The Switch balance loss
    is sown into the ``losses`` collection; PipelineLM's scan-over-layers
    captures it explicitly (``mutable=["losses"]``) and threads it
    through the scan carry and the stage psum, so pipelining never drops
    the balancing pressure (the failure mode the pre-round-4 loud
    rejection guarded against)."""
    n_heads: int
    d_model: int
    d_ff: int
    dtype: object = None
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25

    @nn.compact
    def __call__(self, x, positions, mask=None):
        h = nn.LayerNorm(dtype=self.dtype)(x)
        h = CausalSelfAttention(self.n_heads, self.d_model,
                                dtype=self.dtype, name="attn")(h, positions)
        x = x + h
        h = nn.LayerNorm(dtype=self.dtype)(x)
        if self.moe_experts:
            from fedml_tpu.models.moe import SwitchFFN
            h = SwitchFFN(self.moe_experts, self.d_model, self.d_ff,
                          capacity_factor=self.moe_capacity_factor,
                          dtype=self.dtype, name="moe")(h, mask=mask)
        else:
            h = nn.Dense(self.d_ff, dtype=self.dtype)(h)
            h = nn.gelu(h)
            h = nn.Dense(self.d_model, dtype=self.dtype)(h)
        return x + h


class PipelineLM:
    """Decoder-only LM with an EXPLICIT stacked-blocks pytree, built for
    pipelining: ``params = {"embed", "blocks" ([L, ...] leaves), "final"}``.

    ``apply_seq`` is the single-device reference (scan over layers);
    ``make_pp_apply`` returns the same function distributed over a
    [stages] mesh.  Embedding and head stay replicated — tiny next to the
    block stack that motivates pp — so only block activations travel."""

    def __init__(self, vocab_size: int, d_model: int = 128, n_heads: int = 4,
                 n_layers: int = 4, d_ff: int = 512, max_len: int = 2048,
                 dtype=None, moe_experts: int = 0,
                 moe_capacity_factor: float = 1.25,
                 moe_aux_weight: float = 0.01, pad_id: int = 0):
        self.n_layers = n_layers
        self.dtype = dtype
        self.block = TransformerBlock(n_heads, d_model, d_ff, dtype=dtype,
                                      moe_experts=moe_experts,
                                      moe_capacity_factor=moe_capacity_factor)
        self.d_model = d_model
        self.vocab_size = vocab_size
        self.max_len = max_len
        self.moe_experts = moe_experts
        self.moe_aux_weight = moe_aux_weight
        self.pad_id = pad_id

        class _Embed(nn.Module):
            dtype = None

            @nn.compact
            def __call__(s, toks, positions):
                x = nn.Embed(vocab_size, d_model, dtype=dtype,
                             name="tok_embed")(toks)
                return x + nn.Embed(max_len, d_model, dtype=dtype,
                                    name="pos_embed")(positions)[None]

        class _Final(nn.Module):
            @nn.compact
            def __call__(s, x):
                return nn.Dense(vocab_size, dtype=dtype, name="lm_head")(
                    nn.LayerNorm(dtype=dtype)(x))

        self._embed = _Embed()
        self._final = _Final()

    def init(self, rng: jax.Array, toks: jax.Array) -> Any:
        t = toks.shape[1]
        positions = jnp.arange(t)
        r_embed, r_blocks, r_final = jax.random.split(rng, 3)
        embed = self._embed.init(r_embed, toks, positions)["params"]
        x = self._embed.apply({"params": embed}, toks, positions)
        block_keys = jax.random.split(r_blocks, self.n_layers)
        blocks = jax.vmap(
            lambda k: self.block.init(k, x, positions)["params"])(block_keys)
        final = self._final.init(r_final, x)["params"]
        return {"embed": embed, "blocks": blocks, "final": final}

    def _run_blocks(self, blocks, x, positions, mask=None):
        """Scan the stacked blocks over ``x``; returns ``(out, balance)``
        where ``balance`` is the SUM of the layers' sown Switch balance
        losses (0.0 for the dense FFN) — the sown collection is captured
        per layer call and threaded through the scan outputs, never
        dropped."""
        def one(h, layer_params):
            y, sown = self.block.apply({"params": layer_params}, h,
                                       positions, mask, mutable=["losses"])
            bal = sum(jax.tree.leaves(sown.get("losses", {})),
                      jnp.float32(0.0))
            return y, bal
        out, bals = jax.lax.scan(one, x, blocks)
        return out, jnp.sum(bals)

    def _pad_mask(self, toks):
        return None if not self.moe_experts \
            else (toks != self.pad_id).astype(jnp.float32)

    def apply_seq(self, params: Any, toks: jax.Array) -> jax.Array:
        """Single-device reference forward: [B, T] -> [B, T, V]."""
        return self.apply_seq_with_aux(params, toks)[0]

    def apply_seq_with_aux(self, params: Any, toks: jax.Array,
                           n_micro: int = 1):
        """``(logits, balance)`` with the batch routed in ``n_micro``
        microbatches — the parity twin of the pipelined forward.  Switch
        routing statistics (f, P) are computed per routing call, so the
        balance loss is defined per microbatch; ``balance`` is the MEAN
        over microbatches (per-microbatch sums over layers), which keeps
        its magnitude comparable across n_micro choices."""
        b, t = toks.shape
        if b % n_micro:
            raise ValueError(f"batch {b} not divisible into "
                             f"{n_micro} microbatches")
        positions = jnp.arange(t)
        x = self._embed.apply({"params": params["embed"]}, toks, positions)
        mask = self._pad_mask(toks)
        xs = x.reshape((n_micro, b // n_micro) + x.shape[1:])
        ms = None if mask is None else \
            mask.reshape((n_micro, b // n_micro, t))

        def one_mb(i):
            return self._run_blocks(params["blocks"], xs[i], positions,
                                    None if ms is None else ms[i])
        outs, bals = jax.lax.map(one_mb, jnp.arange(n_micro))
        y = outs.reshape((b, t, self.d_model))
        return (self._final.apply({"params": params["final"]}, y),
                jnp.mean(bals))

    # ---- pipeline execution ---------------------------------------------
    def pp_shard_params(self, params: Any, mesh: Mesh, n_stages: int) -> Any:
        """PLACEMENT-only: the canonical [L, ...] block leaves are
        device_put with the leading layer axis split over the stages axis
        (layer l lives on stage l // (L/S)); embed/final replicated.  The
        pytree SHAPE is unchanged — pipelined and sequential params are
        the same tree, so optimizers, aggregation, and the wire protocol
        never see a pp-specific layout, and make_pp_apply accepts host
        params directly (GSPMD moves them on first call)."""
        if self.n_layers % n_stages:
            raise ValueError(f"n_layers={self.n_layers} not divisible by "
                             f"n_stages={n_stages}")
        blocks = jax.tree.map(
            lambda v: jax.device_put(v, NamedSharding(mesh, P("stages"))),
            params["blocks"])
        rep = lambda t: jax.tree.map(
            lambda v: jax.device_put(v, NamedSharding(mesh, P())), t)
        return {"embed": rep(params["embed"]), "blocks": blocks,
                "final": rep(params["final"])}

    def make_pp_apply(self, mesh: Mesh, n_micro: int,
                      with_aux: bool = False):
        """Returns ``fn(pp_params, toks) -> logits`` (or
        ``(logits, balance)`` when ``with_aux``) running the block stack
        as a GPipe pipeline over ``mesh``'s stages axis.  ``toks`` batch
        must divide into ``n_micro`` microbatches.

        With MoE blocks the Switch balance loss is accumulated in the
        schedule's scan carry — gated on the fill/drain bubble (a stage
        processing the zero-init placeholder must not add routing
        pressure), psum'd over stages, and averaged over microbatches —
        exactly ``apply_seq_with_aux(..., n_micro)``'s definition, which
        is the parity oracle.  The pad mask rides the same ppermute
        hand-off as the activations so each stage routes with its
        in-flight microbatch's mask."""
        n_stages = mesh.shape["stages"]
        if self.n_layers % n_stages:
            raise ValueError(f"n_layers={self.n_layers} not divisible by "
                             f"n_stages={n_stages}")

        def fn(params, toks):
            b, t = toks.shape
            if b % n_micro:
                raise ValueError(f"batch {b} not divisible into "
                                 f"{n_micro} microbatches")
            positions = jnp.arange(t)
            x = self._embed.apply({"params": params["embed"]}, toks,
                                  positions)
            x_mb = x.reshape((n_micro, b // n_micro) + x.shape[1:])
            # the pad mask rides the schedule only when MoE routing needs
            # it — dense pipelines keep the lean (act, out) carry
            moe = bool(self.moe_experts)
            m_mb = (self._pad_mask(toks).reshape(n_micro, b // n_micro, t)
                    if moe else jnp.zeros((0,), jnp.float32))

            @partial(jax.shard_map, mesh=mesh,
                     in_specs=(P("stages"), P(), P()),
                     out_specs=(P(), P()))
            def pipeline(blocks_sharded, xm, mm):
                # in_specs P("stages") splits the canonical [L, ...] layer
                # axis: this device already holds ITS [L/S, ...] stack
                sp = blocks_sharded
                s = jax.lax.axis_index("stages")

                def step(carry, ti):
                    act, msk, out, bal = carry
                    mi = jnp.clip(ti, 0, n_micro - 1)
                    inp = jnp.where(s == 0, xm[mi], act)
                    m_in = jnp.where(s == 0, mm[mi], msk) if moe else None
                    y, b_step = self._run_blocks(sp, inp, positions, m_in)
                    # stage s holds microbatch ti - s; outside [0, M) it
                    # is chewing the zero-init bubble — no balance
                    valid = (ti - s >= 0) & (ti - s < n_micro)
                    bal = bal + jnp.where(valid, b_step, 0.0)
                    if n_stages > 1:
                        hop = [(i, i + 1) for i in range(n_stages - 1)]
                        nxt = jax.lax.ppermute(y, "stages", hop)
                        nxt_m = jax.lax.ppermute(m_in, "stages", hop) \
                            if moe else msk
                    else:
                        nxt, nxt_m = y, (m_in if moe else msk)
                    oidx = ti - (n_stages - 1)
                    write = (s == n_stages - 1) & (oidx >= 0)
                    upd = jax.lax.dynamic_update_index_in_dim(
                        out, y, jnp.clip(oidx, 0, n_micro - 1), 0)
                    out = jnp.where(write, upd, out)
                    return (nxt, nxt_m, out, bal), None

                # the carry becomes device-varying inside the loop (each
                # stage holds different activations); mark the zero init
                # accordingly or the scan typecheck rejects it (same
                # pattern as cohort.py's sharded path)
                msk0 = (jnp.zeros_like(mm[0]) if moe
                        else jnp.zeros((0,), jnp.float32))
                init = jax.lax.pcast(
                    (jnp.zeros_like(xm[0]), msk0,
                     jnp.zeros_like(xm), jnp.float32(0.0)),
                    ("stages",), to="varying")
                (_, _, out, bal), _ = jax.lax.scan(
                    step, init, jnp.arange(n_micro + n_stages - 1))
                # only the last stage holds real outputs; psum replicates
                out = jnp.where(s == n_stages - 1, out,
                                jnp.zeros_like(out))
                return (jax.lax.psum(out, "stages"),
                        jax.lax.psum(bal, "stages") / n_micro)

            y, bal = pipeline(params["blocks"], x_mb, m_mb)
            y = y.reshape((b, t, self.d_model))
            logits = self._final.apply({"params": params["final"]}, y)
            return (logits, bal) if with_aux else logits

        return fn


@dataclasses.dataclass(frozen=True)
class _PPWorkload(Workload):
    """Workload whose params are PipelineLM's explicit pytree (no flax
    'params' collection to unwrap) and whose forward is an explicit
    callable (PipelineLM has no flax ``.apply``)."""
    forward: Any = None  # forward(params, toks) -> logits

    def init(self, rng, sample_batch):
        return self.model.init(rng, sample_batch["x"])

    def apply(self, params, x, train=False, rng=None):
        return self.forward(params, x)


def _nwp_workload_over(plm: PipelineLM, forward_aux, pad_id: int) -> Workload:
    """NWP loss/metrics (the shared make_nwp_loss_metrics semantics) over
    an arbitrary ``forward_aux(params, toks) -> (logits, balance)`` — the
    pipelined workload and its sequential parity twin.  The Switch
    balance term enters the loss at ``plm.moe_aux_weight`` (the same
    alpha convention as NWPWorkload's sown-loss capture); it is 0.0 for
    dense blocks."""
    if plm.moe_experts and pad_id != plm.pad_id:
        # routing masks with plm.pad_id (inside the forward), the loss
        # masks with this pad_id — diverging silently would let padding
        # eat expert capacity while the loss ignores it
        raise ValueError(
            f"pad_id={pad_id} disagrees with the model's routing pad_id="
            f"{plm.pad_id}; build PipelineLM(pad_id={pad_id}) instead")

    def fwd(params, x, rng, train):
        logits, bal = forward_aux(params, x)
        return logits, plm.moe_aux_weight * bal

    loss_fn, metric_fn = make_nwp_loss_metrics(fwd, pad_id)
    return _PPWorkload(model=plm, loss_fn=loss_fn, metric_fn=metric_fn,
                       grad_clip_norm=None,
                       forward=lambda p, x: forward_aux(p, x)[0])


def make_pp_nwp_workload(plm: PipelineLM, mesh: Mesh, n_micro: int,
                         pad_id: int = 0) -> Workload:
    """Next-word-prediction Workload whose forward runs the GPipe
    pipeline — plugs pipeline parallelism into every Workload consumer
    (the local trainer, evaluators, the cross-silo silo train_fn), so a
    silo can train a model too deep for one chip over its local [stages]
    mesh.

    Scope: SILO-LOCAL training (make_local_trainer directly).  The
    vmapped cohort engine cannot consume it — a shard_map pipeline under
    vmap-over-clients is not a meaningful composition (each client would
    need its own stage mesh); federated use is cross-silo, where
    aggregation rides the wire and each silo runs this workload on its
    own chips.  Params come from ``plm.init`` and should be placed with
    ``plm.pp_shard_params`` before training."""
    return _nwp_workload_over(
        plm, plm.make_pp_apply(mesh, n_micro, with_aux=True), pad_id)


def make_seq_nwp_workload(plm: PipelineLM, pad_id: int = 0,
                          n_micro: int = 1) -> Workload:
    """The single-device reference twin of make_pp_nwp_workload (same
    params pytree, apply_seq forward) — the parity oracle.  For MoE
    models pass the pipeline's ``n_micro``: Switch routing statistics
    are per routing call, so the balance loss only matches under the
    same microbatching."""
    return _nwp_workload_over(
        plm, lambda p, x: plm.apply_seq_with_aux(p, x, n_micro), pad_id)
