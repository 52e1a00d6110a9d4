"""Switch-style mixture-of-experts FFN — the expert-parallel (ep) member
of the parallelism family.

The reference has no MoE capability (its NLP zoo stops at LSTMs,
fedml_api/model/nlp/rnn.py); this layer exists because expert parallelism
is a first-class sharding for the framework (alongside dp/tp/sp/pp): the
expert tables carry an explicit leading ``[E, ...]`` axis and all routing
is dense einsums over it, so GSPMD shards experts across an ``experts``
mesh axis with no manual collectives (parallel/expert.py) — the
all-to-all dispatch/combine falls out of the einsum shardings, the
scaling-book way.

Routing follows Fedus et al. 2021 (Switch Transformer): top-1 router,
capacity-bounded dispatch (tokens over capacity are DROPPED and ride the
residual connection), and the load-balancing auxiliary loss
``E * Σ_e f_e·P_e`` sown into the ``losses`` collection (NWPWorkload adds
it to the CE loss when the model carries experts; ``sow`` is a silent
no-op under plain apply, so eval paths need no changes).

Two deliberate departures from the naive formulation:

* **Grouped routing** (the mesh-TF/Switch "group" dim): tokens are routed
  within fixed-size groups, so the dispatch tensor is [G, g, E, C] with
  C = ceil(cf·g/E) — linear in total token count, where one global group
  would be quadratic (at B=2, T=2048, D=256 the one-group dispatch
  einsum would cost more than the expert FFNs themselves).
* **Pad masking**: padded positions (and zeroed federated batch rows)
  share one embedding, so unmasked they would all route to the same
  expert, eat its capacity, and pull the balance loss toward spreading
  padding instead of real tokens.  ``mask`` removes them from dispatch
  and from the f/P statistics; their output is 0, riding the residual,
  and the workload's loss mask ignores them anyway.

Everything is static-shaped and scan/vmap-friendly: argmax + cumsum +
one_hot + einsum — no sorting, no dynamic shapes, nothing that blocks the
MXU (SURVEY.md "XLA semantics").
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


def _auto_group(n_tok: int, target: int = 512, min_group: int = 64) -> int:
    """Largest divisor of ``n_tok`` in [min_group, target], else n_tok
    (realistic B*T values have power-of-two factors)."""
    for g in range(min(target, n_tok), min_group - 1, -1):
        if n_tok % g == 0:
            return g
    return n_tok


class SwitchFFN(nn.Module):
    """Top-1 MoE FFN: [B, T, D] -> [B, T, D] with E experts.

    ``capacity_factor`` bounds each expert's per-group token buffer at
    ``ceil(cf * g / E)``: static shapes for XLA, graceful drop for hot
    experts.  ``group_size=0`` picks the largest divisor of B*T up to
    512.  ``mask`` is [B, T] (1 = real token); None routes everything.
    The router always runs f32 (softmax is range-sensitive; matches the
    workloads' f32-loss convention)."""
    n_experts: int
    d_model: int
    d_ff: int
    capacity_factor: float = 1.25
    group_size: int = 0
    dtype: object = None

    @nn.compact
    def __call__(self, x, mask: Optional[jax.Array] = None):
        b, t, d = x.shape
        n_tok = b * t
        e = self.n_experts
        g = self.group_size or _auto_group(n_tok)
        if n_tok % g:
            raise ValueError(f"group_size {g} must divide B*T = {n_tok}")
        n_groups = n_tok // g
        cap = max(1, int(-(-self.capacity_factor * g // e)))
        xt = x.reshape(n_groups, g, d)
        m = (jnp.ones((n_groups, g), jnp.float32) if mask is None
             else mask.reshape(n_groups, g).astype(jnp.float32))

        # -- top-1 routing (f32), pads excluded ---------------------------
        router_logits = nn.Dense(e, dtype=jnp.float32, name="router")(
            xt.astype(jnp.float32))                          # [G, g, E]
        probs = jax.nn.softmax(router_logits, axis=-1)
        expert = jnp.argmax(probs, axis=-1)                  # [G, g]
        gate = jnp.max(probs, axis=-1) * m                   # [G, g]
        oh = jax.nn.one_hot(expert, e, dtype=jnp.float32) \
            * m[:, :, None]                                  # [G, g, E]

        # load-balance aux (Switch eq. 4) over REAL tokens only
        denom = jnp.maximum(jnp.sum(m), 1.0)
        f_frac = jnp.sum(oh, axis=(0, 1)) / denom
        p_mean = jnp.sum(probs * m[:, :, None], axis=(0, 1)) / denom
        self.sow("losses", "load_balance", e * jnp.sum(f_frac * p_mean))

        # -- capacity-bounded dispatch tensor [G, g, E, C] -----------------
        # per-group position of each token in its expert's buffer; one_hot
        # of an out-of-range position is all-zero, which IS the token drop
        pos = jnp.cumsum(oh, axis=1) - 1.0
        pos_in_e = jnp.sum(pos * oh, axis=-1).astype(jnp.int32)  # [G, g]
        disp = oh[..., None] * jax.nn.one_hot(
            pos_in_e, cap, dtype=jnp.float32)[:, :, None, :]  # [G, g, E, C]

        # -- expert FFN over the explicit [E, ...] tables ------------------
        dt = self.dtype or x.dtype
        w1 = self.param("w1", nn.initializers.lecun_normal(),
                        (e, d, self.d_ff), jnp.float32)
        b1 = self.param("b1", nn.initializers.zeros, (e, self.d_ff),
                        jnp.float32)
        w2 = self.param("w2", nn.initializers.lecun_normal(),
                        (e, self.d_ff, d), jnp.float32)
        b2 = self.param("b2", nn.initializers.zeros, (e, d), jnp.float32)

        xe = jnp.einsum("gnec,gnd->gecd", disp.astype(dt), xt.astype(dt))
        h = jnp.einsum("gecd,edf->gecf", xe, w1.astype(dt)) \
            + b1.astype(dt)[None, :, None, :]
        h = nn.gelu(h)
        ye = jnp.einsum("gecf,efd->gecd", h, w2.astype(dt)) \
            + b2.astype(dt)[None, :, None, :]

        # -- combine (gate-weighted; dropped/pad tokens come back as 0) ----
        comb = (disp * gate[..., None, None]).astype(dt)
        yt = jnp.einsum("gnec,gecd->gnd", comb, ye)
        return yt.reshape(b, t, d).astype(x.dtype)


# ---------------------------------------------------------------------------
# top-k routing (sigmoid with a selection bias, or softmax), no dropped
# token, shared experts or none, and a layer that holds a share of the
# experts

def route_sigmoid_topk(logits, select_bias, k: int, scale: float,
                       normalize: bool = True):
    """``(chosen [..., k] int32, weights [..., k] f32)`` of the
    auxiliary-loss-free router (``topk_method: noaux_tc`` with one group):
    scores are ``sigmoid(logits)`` over ALL experts, the ``k`` chosen are
    the top ``k`` of ``score + select_bias``, and the weights are the
    chosen experts' own scores (the bias only selects), normalised over
    the ``k`` chosen and scaled.  The choice is an index, so no gradient
    reaches ``select_bias``."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, chosen = jax.lax.top_k(scores + select_bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), w * scale


def route_softmax_topk(logits, k: int, normalize: bool = True):
    """``(chosen [..., k] int32, weights [..., k] f32)`` of the softmax
    router (Qwen3-MoE's, Keye-VL-2.0's): probabilities over ALL experts,
    the ``k`` largest chosen, their own probabilities as weights,
    normalised over the ``k`` chosen where ``norm_topk_prob``.  No
    selection bias, no scaling."""
    w, chosen = jax.lax.top_k(
        jax.nn.softmax(logits.astype(jnp.float32), axis=-1), k)
    if normalize:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen.astype(jnp.int32), w


def plan_held_tiles(chosen, first_held: int, held: int, tile: int):
    """Where each (token, choice) goes in a layout sorted by held expert.

    ``chosen`` [N, k] are expert ids over all experts; the experts
    ``first_held .. first_held + held - 1`` live here.  The layout has one
    run of rows an expert, each padded to a whole number of ``tile`` rows,
    so a tile belongs to one expert; its length ``L`` is the worst case
    (every choice of every token held) and only the first ``n_active``
    tiles hold a row.  The layout is never built: it is a list of indices.
    Returns ``row_choice`` [L] (the flat index ``token * k + choice`` of
    each row's choice, ``N * k`` for a padding row, so ``row_choice // k``
    is the row's token and ``N`` a padding row's), ``tile_expert``
    [L / tile], ``n_active`` and ``counts`` [held] (tokens each held expert
    takes)."""
    n, k = chosen.shape
    cap = n * min(k, held)
    length = -(-cap // tile) * tile + held * tile
    local = chosen.reshape(-1) - first_held
    is_held = (local >= 0) & (local < held)
    onehot = ((local[:, None] == jnp.arange(held)[None, :])
              & is_held[:, None]).astype(jnp.int32)            # [A, held]
    running = jnp.cumsum(onehot, axis=0)
    counts = running[-1]
    rank = jnp.sum(running * onehot, axis=-1) - 1
    padded = -(-counts // tile) * tile
    ends = jnp.cumsum(padded)
    starts = ends - padded
    dest = jnp.where(is_held,
                     starts[jnp.clip(local, 0, held - 1)] + rank, length)
    row_choice = jnp.full((length + 1,), n * k, jnp.int32).at[dest].set(
        jnp.arange(n * k, dtype=jnp.int32))[:length]
    tile_expert = jnp.clip(jnp.searchsorted(
        ends, jnp.arange(length // tile) * tile, side="right"),
        0, held - 1).astype(jnp.int32)
    return (row_choice, tile_expert, (ends[-1] // tile).astype(jnp.int32),
            counts)


def _silu_gate(xt, wg, wu):
    g = xt @ wg
    u = xt @ wu
    return g, u, jax.nn.silu(g) * u


@jax.custom_vjp
def grouped_gated_mlp(xp, wg, wu, wd, row_weight, row_token, tile_expert,
                      n_active):
    """The grouped product over the experts held, summed into the tokens:
    ``y[t] = sum_j row_weight[j] W_down[e](silu(W_gate[e] x_t) * W_up[e]
    x_t)`` over the rows ``j`` of `plan_held_tiles`' layout whose token is
    ``t``, ``e`` the expert of ``j``'s tile, ``x`` the rows of ``xp``
    [N + 1, d] (its last row zeros, what a padding row reads).  A tile's
    results are added into its tokens as it is computed, so no array of
    the layout's length and the model's width exists.  Only the
    ``n_active`` tiles that hold a row are computed: a loop with a
    data-dependent trip count, which is why the gradient is written out
    below and not traced.  Returns [N + 1, d]; a padding row's weight is
    0, so the last row stays zeros."""
    return _grouped_fwd_sum(xp, wg, wu, wd, row_weight, row_token,
                            tile_expert, n_active)


def _tile(row_weight, row_token, tile_expert, j):
    """The tokens, row weights and expert of tile ``j``."""
    tile = row_token.shape[0] // tile_expert.shape[0]
    return (jax.lax.dynamic_slice(row_token, (j * tile,), (tile,)),
            jax.lax.dynamic_slice(row_weight, (j * tile,), (tile,)),
            tile_expert[j])


def _grouped_fwd_sum(xp, wg, wu, wd, row_weight, row_token, tile_expert,
                     n_active):
    def body(j, y):
        tok, rw, e = _tile(row_weight, row_token, tile_expert, j)
        _, _, h = _silu_gate(xp[tok], wg[e], wu[e])
        return y.at[tok].add((rw[:, None] * (h @ wd[e])).astype(y.dtype))

    return jax.lax.fori_loop(0, n_active, body, jnp.zeros_like(xp))


def _grouped_fwd(xp, wg, wu, wd, row_weight, row_token, tile_expert,
                 n_active):
    y = _grouped_fwd_sum(xp, wg, wu, wd, row_weight, row_token,
                         tile_expert, n_active)
    return y, (xp, wg, wu, wd, row_weight, row_token, tile_expert, n_active)


def _grouped_bwd(res, dy):
    xp, wg, wu, wd, row_weight, row_token, tile_expert, n_active = res
    tile = row_token.shape[0] // tile_expert.shape[0]

    def body(j, carry):
        dxp, dwg, dwu, dwd, drw = carry
        tok, rw, e = _tile(row_weight, row_token, tile_expert, j)
        xt, dyt = xp[tok], dy[tok]
        g, u, h = _silu_gate(xt, wg[e], wu[e])
        gt = dyt @ wd[e].T
        # the row weight's gradient is <W_down h, dy> = <h, W_down^T dy>
        drw = jax.lax.dynamic_update_slice(
            drw, jnp.sum(h * gt, axis=-1).astype(drw.dtype), (j * tile,))
        dh = rw[:, None] * gt
        sig = jax.nn.sigmoid(g)
        dg = dh * u * (sig * (1.0 + g * (1.0 - sig)))
        du = dh * g * sig
        dwd = dwd.at[e].add((h.T @ (rw[:, None] * dyt)).astype(dwd.dtype))
        dwg = dwg.at[e].add((xt.T @ dg).astype(dwg.dtype))
        dwu = dwu.at[e].add((xt.T @ du).astype(dwu.dtype))
        dxt = dg @ wg[e].T + du @ wu[e].T
        return dxp.at[tok].add(dxt.astype(dxp.dtype)), dwg, dwu, dwd, drw

    dxp, dwg, dwu, dwd, drw = jax.lax.fori_loop(
        0, n_active, body, tuple(jnp.zeros_like(a)
                                 for a in (xp, wg, wu, wd, row_weight)))
    # the padding row of xp is a constant zero: nothing flows into it
    return dxp.at[-1].set(0), dwg, dwu, dwd, drw, None, None, None


grouped_gated_mlp.defvjp(_grouped_fwd, _grouped_bwd)


def held_expert_sum(xt, chosen, w, wg, wu, wd, first_held: int, tile: int):
    """``(y [N, d], counts [held])``: what the held experts add to each of
    the ``N`` tokens of ``xt`` [N, d] under the router's ``chosen`` [N, k]
    and weights ``w`` [N, k], a choice whose expert is not held adding
    nothing.  The weights reach the rows of the layout through a gather
    of ``L`` scalars."""
    n, d = xt.shape
    row_choice, tile_expert, n_active, counts = plan_held_tiles(
        chosen, first_held, wg.shape[0], tile)
    xp = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)])
    row_weight = jnp.concatenate([w.reshape(-1).astype(xt.dtype),
                                  jnp.zeros((1,), xt.dtype)])[row_choice]
    y = grouped_gated_mlp(xp, wg, wu, wd, row_weight,
                          row_choice // chosen.shape[1], tile_expert,
                          n_active)
    return y[:n], counts


class GatedMLP(nn.Module):
    """``W_down(silu(W_gate x) * W_up x)``, no biases."""
    d_ff: int
    init_std: float = 0.02
    dtype: object = None

    @nn.compact
    def __call__(self, x):
        def dense(n, name):
            return nn.Dense(n, use_bias=False, dtype=self.dtype, name=name,
                            kernel_init=nn.initializers.normal(
                                self.init_std))
        h = nn.silu(dense(self.d_ff, "gate")(x)) * dense(self.d_ff, "up")(x)
        return dense(x.shape[-1], "down")(h)


class HeldExpertMoE(nn.Module):
    """An expert layer as one chip of an expert-parallel deployment holds
    it: ``experts_total`` routed experts of which ``experts_held`` (ids
    ``first_held ..``) live here, ``top_k`` a token, and ``n_shared``
    shared experts every token passes (0: none).  ``router`` names the
    rule: ``sigmoid`` is `route_sigmoid_topk` (DeepSeek-V3 / GLM-4.7: a
    ``select_bias`` leaf, ``scale``), ``softmax`` is `route_softmax_topk`
    (Keye-VL-2.0: no such leaf).  The router has its published width and
    the weights are normalised over all ``top_k`` chosen, held or not; the
    layer returns its own experts' part of the result plus the shared
    experts', and what the absent experts would add is left out (their
    chips', in a deployment).  No capacity and no dropped token: the
    chosen (token, expert) pairs are gathered by expert into
    `grouped_gated_mlp`, which adds each tile's results into its tokens
    (`held_expert_sum`).

    Sows ``moe_stats/counts``, float32 ``[tokens, assignments, held
    assignments, largest held expert's tokens, mean held expert's
    tokens]`` of this call (read only by a caller that asks for the
    collection)."""
    experts_total: int
    experts_held: int
    first_held: int
    top_k: int
    d_ff: int
    n_shared: int = 1
    scale: float = 1.0
    normalize: bool = True
    init_std: float = 0.02
    tile: int = 512
    dtype: object = None
    router: str = "sigmoid"

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        n, held = b * t, self.experts_held
        if self.router not in ("sigmoid", "softmax"):
            raise ValueError(f"no router {self.router!r}")
        if not 0 <= self.first_held <= self.experts_total - held:
            raise ValueError(
                f"experts {self.first_held}..{self.first_held + held - 1} "
                f"are not among the {self.experts_total} routed")
        init = nn.initializers.normal(self.init_std)
        xt = x.reshape(n, d)
        router = self.param("router", init, (d, self.experts_total),
                            jnp.float32)
        # the choice is discrete: a logit a rounding away moves a token
        logits = jnp.dot(xt.astype(jnp.float32), router,
                         precision=jax.lax.Precision.HIGHEST)
        if self.router == "softmax":
            chosen, w = route_softmax_topk(logits, self.top_k,
                                           self.normalize)
        else:
            bias = self.param("select_bias", init, (self.experts_total,),
                              jnp.float32)
            chosen, w = route_sigmoid_topk(logits, bias, self.top_k,
                                           self.scale, self.normalize)
        wg = self.param("experts_gate", init, (held, d, self.d_ff),
                        jnp.float32)
        wu = self.param("experts_up", init, (held, d, self.d_ff),
                        jnp.float32)
        wd = self.param("experts_down", init, (held, self.d_ff, d),
                        jnp.float32)
        dt = self.dtype or x.dtype
        tile = min(self.tile, max(8, -(-n // 8) * 8))
        y, counts = held_expert_sum(xt.astype(dt), chosen, w, wg.astype(dt),
                                    wu.astype(dt), wd.astype(dt),
                                    self.first_held, tile)
        if self.n_shared:
            y = y + GatedMLP(self.n_shared * self.d_ff, self.init_std,
                             self.dtype, name="shared")(xt)
        load = counts.astype(jnp.float32)
        self.sow("moe_stats", "counts", jnp.stack([
            jnp.float32(n), jnp.float32(n * self.top_k), jnp.sum(load),
            jnp.max(load), jnp.mean(load)]))
        return y.reshape(b, t, d).astype(x.dtype)
