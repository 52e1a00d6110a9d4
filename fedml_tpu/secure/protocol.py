"""Live secure-aggregation round protocol over the real transport.

`secure/secagg.py` proves the ring algebra in simulation (one jit, one
process); this module is the DISTRIBUTED protocol — the practical-SecAgg
construction (Bonawitz et al. 2017) spoken over `Message` frames between
real actors, composed with the repo's admission, streaming-fold, and
observability seams (ROADMAP item 3):

* **mask agreement** — each silo of a round's masking group advertises a
  DH public key (``pk_i = g^sk_i mod p``, the binding commitment to its
  pairwise secret) plus t-of-N Shamir shares of BOTH its pairwise secret
  ``sk_i`` and its self-mask seed ``b_i`` (`field.bgw_encode`), addressed
  per peer.  The server relays: one ROSTER frame per silo carries the
  cohort's public keys and the shares addressed to it.  Pairwise seeds
  derive without any pair ever talking directly:
  ``s_ij = pk_j^sk_i = g^(sk_i*sk_j) = pk_i^sk_j`` — symmetric.
* **masked upload** — the silo quantizes its weighted update into the
  uint32 ring (clip → fixed-point; the scale auto-derives from the group
  size so the cohort sum cannot wrap — `secagg.ring_budget_scale`),
  then adds the pairwise masks (``+PRG(s_ij)`` for ``j > i``, ``−`` for
  ``j < i``) and its self-mask ``PRG(b_i)``.  The payload carries the
  masked update tree AND a masked quantized weight scalar, so the server
  recovers the exact weighted mean as ``Σ q(x_i·u_i) / Σ q(u_i)`` —
  the weight normalizer cancels in the ratio.
* **ring fold** — the server folds each admitted masked upload into
  O(model) standing uint32 state at arrival (ring addition IS the fold),
  preserving the PR 7 O(1)-memory spine; nothing cohort-sized is held.
* **unmask** — at barrier close the server asks the survivors for the
  shares it needs: self-mask-seed shares of every UPLOADER (their
  ``PRG(b_i)`` must leave the sum) and pairwise-secret shares of every
  DEAD roster member (their stray ``±PRG(s_ij)`` terms must leave the
  sum — the dropout-recovery path, fed by the straggler policy and the
  PR 1 `FailureDetector`).  Shamir reconstruction (`field.bgw_decode`)
  needs any t of the N shares, so the round survives up to
  ``len(roster) − t`` dropouts and fails LOUDLY beyond that.  A silo
  never reveals both share kinds for the same peer (revealing ``sk_j``
  AND ``b_j`` would unmask a live upload) — enforced client-side.

Threat model (the README table is the full statement): the server learns
only the cohort SUM; individual updates never cross the wire in
plaintext and a silo's masked frame is information-free without t
colluding share holders.  Share envelopes ride the server relay
UNENCRYPTED in this implementation — an actively malicious server (or an
observer of every link) could reassemble seeds; the known fix is
peer-to-peer envelope encryption under the same DH keys (a second
agreement round-trip), documented as future hardening.  The server here
is honest-but-curious: it relays envelopes without combining them.

Everything is host-side numpy at message rate (the admission-pipeline
discipline — no jit, nothing for the recompile sentry to watch); the
PRG is jax's threefry bit stream so both ends of a pair derive identical
masks on any backend.
"""

from __future__ import annotations

import dataclasses
import hashlib
import logging
import math
import secrets as _secrets
import threading
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from fedml_tpu.obs import telemetry
from fedml_tpu.secure.field import P_DEFAULT, bgw_decode, bgw_encode
from fedml_tpu.secure.secagg import ring_budget_scale

log = logging.getLogger(__name__)

SECAGG_MODES = ("off", "pairwise", "grouped")

# message types: continue the shared numbering (cross_silo.MsgType 1-6,
# async MSG_RETASK_TICK 7, hierarchical MSG_EDGE_TIMEOUT 8)
MSG_SECAGG_ADVERT = 9   # silo -> server: pk + per-peer Shamir shares
MSG_SECAGG_ROSTER = 10  # server -> silo: cohort pks + shares addressed to it
MSG_SECAGG_UNMASK = 11  # server -> silo: survivors/dead share request
MSG_SECAGG_SHARES = 12  # silo -> server: the revealed shares

# DH generator in Z_p (p = 2^31 - 1, Mersenne).  31-bit DH is a
# protocol-shape demonstrator, not production-strength key agreement —
# the README threat model says so explicitly.
GENERATOR = 7
_P = int(P_DEFAULT)


class SecAggError(RuntimeError):
    """Loud protocol failure: too few shares to unmask, a commitment
    mismatch, or a wrapped/degenerate sum — the round is LOST, never
    silently mis-aggregated."""


# ---------------------------------------------------------------------------
# ring arithmetic helpers (host numpy; exact two's-complement fixed point)
# ---------------------------------------------------------------------------

def quantize_np(x: np.ndarray, scale: float, clip: float) -> np.ndarray:
    """Clip to ±clip, fixed-point encode into the uint32 ring (two's
    complement for negatives) — the host-numpy twin of `secagg.quantize`."""
    q = np.round(np.clip(np.asarray(x, np.float64), -clip, clip)
                 * scale).astype(np.int64).astype(np.int32)
    return q.view(np.uint32)


def dequantize_np(q: np.ndarray, scale: float) -> np.ndarray:
    return q.astype(np.uint32).view(np.int32).astype(np.float64) / scale


def _flat_leaves(tree) -> List[np.ndarray]:
    """Canonical leaf order shared with the admission pipeline (sorted
    Mapping keys), so the masked template fingerprint and the mask PRG
    walk the same sequence everywhere."""
    from fedml_tpu.robust.admission import _leaves
    return _leaves(tree)


def _tree_map_np(fn, tree):
    """Structure-preserving map over dict/list/tuple/leaf nests (the wire
    payload shapes `Message` carries) without requiring jax pytree
    registration of decoded read-only views."""
    if hasattr(tree, "items"):
        return {k: _tree_map_np(fn, v) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        out = [_tree_map_np(fn, v) for v in tree]
        return tuple(out) if isinstance(tree, tuple) else out
    return fn(np.asarray(tree))


def prg_mask(seed: int, round_idx: int, shapes: List[tuple]) -> List[np.ndarray]:
    """Deterministic uint32 mask stream for one (seed, round): leaf i of
    the payload gets ``bits(fold_in(fold_in(key(seed), round), i))``.
    Both ends of a pair call this with the same seed and MUST get the
    same words — jax's threefry is deterministic across processes and
    backends, which is why this is not np.random."""
    key = jax.random.fold_in(jax.random.key(int(seed) & 0x7FFFFFFFFFFFFFFF),
                             int(round_idx) & 0xFFFFFFFF)
    out = []
    for i, shape in enumerate(shapes):
        k = jax.random.fold_in(key, i)
        out.append(np.asarray(jax.random.bits(k, shape, jax.numpy.uint32)))
    return out


def payload_scale(group_size: int, clip: float) -> float:
    """The round's fixed-point scale, derived IDENTICALLY by every
    client and server from (group size, clip).  The masked payload has
    two channels sharing one scale: the value tree (entries bounded by
    ±clip) and the weight scalar (bounded by 1.0) — the budget must hold
    for BOTH, so the bound is max(clip, 1): with a sub-1 clip the value
    channel alone would allow a scale large enough for N full weights to
    wrap the ring."""
    return ring_budget_scale(group_size, max(float(clip), 1.0))


def masked_template(params) -> Dict[str, object]:
    """The structural contract of a masked upload: the params tree with
    every leaf quantized to uint32, plus the masked weight scalar.  The
    admission pipeline fingerprints THIS (kind="masked"), so structure
    screens run pre-mask-removal exactly as the plaintext path screens
    plaintext uploads."""
    q = _tree_map_np(lambda l: np.zeros(np.shape(l), np.uint32), params)
    return {"q": q, "w": np.zeros((1,), np.uint32)}


def _apply_mask_inplace(leaves: List[np.ndarray],
                        masks: List[np.ndarray], sign: int) -> None:
    """In-place ± masks, leafwise in canonical order.  Every mask site
    owns its target exclusively — the client's payload is freshly
    quantized (nothing else references it) and the server's accumulator
    is consumed by the round's finalize — so the N-masks-per-upload and
    S+D·S-removals-per-unmask passes never pay a full-model copy each."""
    assert len(leaves) == len(masks)
    for a, m in zip(leaves, masks):
        if sign > 0:
            a += m
        else:
            a -= m


def _rebuild_like(tree, new_leaves: List[np.ndarray]):
    """Re-nest flat leaves into tree's structure (canonical key order —
    the inverse of `_flat_leaves`)."""
    pos = [0]

    def walk(t):
        if hasattr(t, "items"):
            return {k: walk(v) for k, v in
                    sorted(t.items(), key=_canon_sort_key)}
        if isinstance(t, (list, tuple)):
            out = [walk(v) for v in t]
            return tuple(out) if isinstance(t, tuple) else out
        leaf = new_leaves[pos[0]]
        pos[0] += 1
        return leaf

    return walk(tree)


def _canon_sort_key(kv):
    from fedml_tpu.robust.admission import _canon_key
    return _canon_key(kv[0])


def _commit(value: int, round_idx: int, owner: int, kind: str) -> str:
    """Binding commitment to a secret seed: published in the advert so
    a reconstruction from (possibly corrupted) shares is VERIFIED before
    its PRG is subtracted from the sum."""
    return hashlib.sha256(
        f"secagg:{kind}:{owner}:{round_idx}:{value}".encode()).hexdigest()


def _as_int_shares(shares: np.ndarray) -> List[int]:
    return [int(s) for s in np.asarray(shares).reshape(-1)]


# ---------------------------------------------------------------------------
# client side
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _ClientRound:
    round_idx: int
    group: List[int]            # sorted transport ids of the masking group
    threshold: int
    clip: float
    scale: float
    weight_cap: float
    sk: int
    b: int
    pks: Optional[Dict[int, int]] = None      # roster pks (after ROSTER)
    roster: Optional[List[int]] = None
    inbound: Optional[Dict[int, Tuple[int, int]]] = None  # peer -> (sk, b) share
    # which share KIND this client already revealed per peer this round:
    # the cross-REQUEST half of the never-both invariant (one request is
    # checked by the survivors∩dead guard; two sequential well-formed
    # requests naming the same peer differently must also be refused)
    revealed: Dict[int, str] = dataclasses.field(default_factory=dict)


class SecAggClient:
    """Silo-side protocol endpoint.

    Stateless across rounds except the current `_ClientRound`; every
    secret (``sk_i``, ``b_i``, Shamir coefficients) draws from
    ``secrets``-grade entropy unless a test injects ``rng``.  The sum is
    EXACT regardless of these draws — masks cancel bit-for-bit — so a
    federation with entropy-seeded clients still reproduces the
    plaintext mean up to quantization."""

    def __init__(self, node_id: int,
                 rng: Optional[np.random.RandomState] = None):
        self.node_id = int(node_id)
        self._rng = rng
        self._round: Optional[_ClientRound] = None
        self._advert: Optional[Dict] = None

    def _rand_field(self) -> int:
        if self._rng is not None:
            return int(self._rng.randint(1, _P))
        return _secrets.randbelow(_P - 1) + 1

    def begin_round(self, round_idx: int, info: Dict) -> Dict:
        """Open a round from the sync frame's ``ARG_SECAGG`` info and
        return the ADVERT payload: the DH public key (commitment to
        ``sk``), the self-mask-seed commitment, and per-peer Shamir
        shares of both secrets.

        Idempotent per round: a duplicated sync frame (chaos dup,
        transport retry) returns the SAME advert instead of re-keying —
        fresh keys behind an already-banked advert would desynchronize
        the masks from what the server relays, and the sum would never
        cancel."""
        r = self._round
        if r is not None and r.round_idx == int(round_idx) \
                and self._advert is not None:
            return self._advert
        group = sorted(int(s) for s in info["group"])
        if self.node_id not in group:
            raise SecAggError(f"silo {self.node_id} tasked with a masking "
                              f"group it is not a member of: {group}")
        threshold = int(info["threshold"])
        clip = float(info["clip"])
        scale = payload_scale(len(group), clip)
        sk = self._rand_field()
        b = self._rand_field()
        n = len(group)
        share_rng = (self._rng if self._rng is not None
                     else np.random.RandomState(np.random.MT19937(
                         np.random.SeedSequence(_secrets.randbits(128)))))
        sk_shares = _as_int_shares(bgw_encode(
            np.asarray([[sk]], np.int64), n, threshold - 1, rng=share_rng))
        b_shares = _as_int_shares(bgw_encode(
            np.asarray([[b]], np.int64), n, threshold - 1, rng=share_rng))
        self._round = _ClientRound(
            round_idx=int(round_idx), group=group, threshold=threshold,
            clip=clip, scale=scale, weight_cap=float(info["weight_cap"]),
            sk=sk, b=b)
        self._advert = {
            # pk doubles as the binding commitment to sk: pair-key
            # reconstructions verify g^sk_rec == pk, so no separate
            # sk commitment rides the wire
            "pk": pow(GENERATOR, sk, _P),
            "b_commit": _commit(b, round_idx, self.node_id, "b"),
            # share index = the peer's position in the sorted group
            "shares": {str(peer): [sk_shares[i], b_shares[i]]
                       for i, peer in enumerate(group)},
        }
        return self._advert

    def has_roster(self, round_idx: int) -> bool:
        r = self._round
        return (r is not None and r.round_idx == int(round_idx)
                and r.roster is not None)

    def on_roster(self, round_idx: int, payload: Dict) -> bool:
        """Bank the cohort's public keys and the shares addressed to this
        silo.  Returns False (and ignores the frame) on a stale round."""
        r = self._round
        if r is None or r.round_idx != int(round_idx):
            return False
        r.roster = sorted(int(s) for s in payload["roster"])
        r.pks = {int(k): int(v) for k, v in payload["pks"].items()}
        r.inbound = {int(k): (int(v[0]), int(v[1]))
                     for k, v in payload.get("shares", {}).items()}
        return True

    def mask(self, round_idx: int, update, num_samples: float) -> Dict:
        """Quantize the weighted update and add every mask.  The weight
        rides the ring too (``u = min(n/weight_cap, 1)`` quantized), so
        the server's recovered ratio is the exact weighted mean and the
        normalizer cancels."""
        r = self._round
        if r is None or r.round_idx != int(round_idx) or r.roster is None:
            raise SecAggError(f"mask() before a round-{round_idx} roster")
        u = min(float(num_samples) / r.weight_cap, 1.0)
        if u <= 0:
            raise SecAggError(f"non-positive masked weight {u}")
        payload = {
            "q": _tree_map_np(
                lambda l: quantize_np(l.astype(np.float64) * u,
                                      r.scale, r.clip), update),
            "w": quantize_np(np.asarray([u]), r.scale, 1.0),
        }
        leaves = _flat_leaves(payload)
        shapes = [l.shape for l in leaves]
        for peer in r.roster:
            if peer == self.node_id:
                continue
            seed = pow(r.pks[peer], r.sk, _P)
            sign = 1 if peer > self.node_id else -1
            _apply_mask_inplace(leaves, prg_mask(seed, r.round_idx, shapes),
                                sign)
        _apply_mask_inplace(leaves, prg_mask(r.b, r.round_idx, shapes), 1)
        return payload

    def reveal(self, round_idx: int, survivors, dead) -> Dict:
        """Answer an UNMASK request: the self-mask-seed shares this silo
        holds for SURVIVORS and the pairwise-secret shares for DEAD
        roster members.  Refuses — loudly — to reveal both kinds for the
        same silo: that pair of shares unmasks a live upload.  The
        refusal is STATEFUL per round: a second, individually well-formed
        request that flips a peer between the survivor and dead sets
        (a compromised/replayed UNMASK frame — legitimate re-requests
        repeat the SAME snapshot) is refused before anything leaves."""
        r = self._round
        if r is None or r.round_idx != int(round_idx) or r.inbound is None:
            raise SecAggError(f"reveal() without round-{round_idx} shares")
        survivors = {int(s) for s in survivors}
        dead = {int(s) for s in dead}
        both = survivors & dead
        if both:
            raise SecAggError(
                f"refusing unmask request naming silos {sorted(both)} as "
                f"BOTH survivor and dead: revealing sk and b together "
                f"would expose a live upload")
        want = {**{p: "b" for p in survivors}, **{p: "sk" for p in dead}}
        flipped = sorted(p for p, kind in want.items()
                         if r.revealed.get(p, kind) != kind)
        if flipped:
            raise SecAggError(
                f"refusing unmask request that flips silos {flipped} "
                f"between survivor and dead across requests: the share "
                f"pair would expose a live upload")
        out = {"b": {}, "sk": {}}
        for peer, (sk_share, b_share) in r.inbound.items():
            kind = want.get(peer)
            if kind is None:
                continue
            r.revealed[peer] = kind
            if kind == "b":
                out["b"][str(peer)] = b_share
            else:
                out["sk"][str(peer)] = sk_share
        return out


# ---------------------------------------------------------------------------
# server side
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _ServerRound:
    round_idx: int
    group: List[int]
    threshold: int
    scale: float
    adverts: Dict[int, Dict] = dataclasses.field(default_factory=dict)
    roster: Optional[List[int]] = None
    acc: Optional[Dict] = None            # running ring sum (uint32 leaves)
    folded: Dict[int, float] = dataclasses.field(default_factory=dict)
    reveals: Dict[int, Dict] = dataclasses.field(default_factory=dict)
    unmask_sent: bool = False


class SecAggServer:
    """Server/edge-side protocol endpoint: relay + ring fold + unmask.

    One instance serves one aggregation point (the flat root, or one
    edge block under ``--secagg grouped``); per-round state lives in a
    `_ServerRound` and is O(model + group) — the fold is ring addition
    into one uint32 tree at arrival, so server memory stays flat in
    cohort size (the PR 7 spine, preserved under masking).

    ``norm_screen_*``: the POST-unmask sum screen — per-silo norms are
    unavailable by construction, so the defense that remains is a
    rolling median+MAD screen over the recovered SUM's update norm (and
    the sum-level clip + weak-DP noise of ``finalize``).  The pre-mask
    screens (structure fingerprint, ``num_samples``) run in the
    admission pipeline against `masked_template`, before the fold.
    """

    def __init__(self, *, threshold: int = 0, clip: float = 2.0**14,
                 weight_cap: float = 1.0, norm_clip: float = 0.0,
                 noise_std: float = 0.0, seed: int = 0,
                 norm_screen_k: float = 6.0, norm_screen_window: int = 64,
                 norm_screen_min_history: int = 8, node: str = "server"):
        if clip <= 0:
            raise ValueError(f"clip must be > 0, got {clip}")
        if weight_cap <= 0:
            raise ValueError(f"weight_cap must be > 0, got {weight_cap}")
        if threshold < 0:
            raise ValueError(f"threshold must be >= 0 (0 = majority), "
                             f"got {threshold}")
        self.threshold_cfg = int(threshold)
        self.clip = float(clip)
        self.weight_cap = float(weight_cap)
        self.norm_clip = float(norm_clip)
        self.noise_std = float(noise_std)
        self.seed = int(seed)
        self.node = node
        self.norm_screen_k = norm_screen_k
        self.norm_screen_min_history = norm_screen_min_history
        import collections
        self._sum_norms = collections.deque(maxlen=norm_screen_window)
        self._round: Optional[_ServerRound] = None
        self._lock = threading.Lock()
        reg = telemetry.get_registry()
        self._c_masked = reg.counter("fedml_secagg_masked_uploads_total")
        self._c_share_frames = reg.counter("fedml_secagg_share_frames_total")
        # envelopes = per-pair Shamir shares relayed (inside adverts) or
        # revealed (inside unmask answers): the O(N^2) [flat] vs O(N^2/E)
        # [grouped] agreement-traffic quantity —
        # frame counts alone are O(N) either way and cannot show it
        self._c_share_env = reg.counter("fedml_secagg_share_envelopes_total")
        self._c_reconstruct = {
            kind: reg.counter("fedml_secagg_unmask_reconstructions_total",
                              kind=kind)
            for kind in ("self_mask", "pair_key")}
        self._c_rounds = reg.counter("fedml_secagg_rounds_total")
        self._c_sum_rejected = reg.counter("fedml_secagg_sum_rejected_total")
        self._h_agreement = reg.histogram("fedml_secagg_agreement_seconds")
        self._h_unmask = reg.histogram("fedml_secagg_unmask_seconds")
        self._agreement_t0: Optional[float] = None

    # -- round lifecycle -----------------------------------------------------
    def _threshold_for(self, n: int) -> int:
        t = self.threshold_cfg or (n // 2 + 1)
        return max(2, min(t, n))

    def round_start(self, round_idx: int, group) -> None:
        import time
        group = sorted(int(s) for s in group)
        if len(group) < 2:
            raise SecAggError(
                f"secure aggregation needs a masking group of >= 2 silos "
                f"(got {group}): a single member's 'sum' IS its update")
        with self._lock:
            self._round = _ServerRound(
                round_idx=int(round_idx), group=group,
                threshold=self._threshold_for(len(group)),
                scale=payload_scale(len(group), self.clip))
        self._agreement_t0 = time.perf_counter()

    def sync_info(self) -> Dict:
        """The ``ARG_SECAGG`` dict the sync broadcast ships: everything a
        client needs to agree on the round's masking parameters without
        any silo-side configuration."""
        r = self._require_round()
        return {"group": list(r.group), "threshold": r.threshold,
                "clip": self.clip, "weight_cap": self.weight_cap}

    def _require_round(self) -> _ServerRound:
        if self._round is None:
            raise SecAggError("no secagg round open")
        return self._round

    # -- mask agreement ------------------------------------------------------
    def note_advert(self, silo: int, payload: Dict) -> bool:
        """Bank one silo's advert; True when the whole group advertised
        (time to flush the roster)."""
        r = self._require_round()
        silo = int(silo)
        with self._lock:
            if silo not in r.group or r.roster is not None:
                return False
            if silo in r.adverts:
                return False  # duplicate delivery (chaos dup)
            self._c_share_frames.inc()
            self._c_share_env.inc(len(payload.get("shares", {})))
            r.adverts[silo] = {
                "pk": int(payload["pk"]),
                "b_commit": payload.get("b_commit"),
                "shares": {int(k): (int(v[0]), int(v[1]))
                           for k, v in payload.get("shares", {}).items()},
            }
            return set(r.adverts) >= set(r.group)

    def advertised(self) -> set:
        r = self._require_round()
        with self._lock:
            return set(r.adverts)

    def roster_ready(self) -> bool:
        r = self._require_round()
        return r.roster is not None

    def roster_members(self) -> List[int]:
        r = self._require_round()
        with self._lock:
            return list(r.roster or [])

    def folded_silos(self) -> List[int]:
        r = self._require_round()
        with self._lock:
            return sorted(r.folded)

    def flush_roster(self, subset=None) -> Dict[int, Dict]:
        """Fix the round's roster (everyone who advertised, or a subset)
        and build each member's ROSTER frame: the cohort pks + the
        shares every peer addressed to it.  Needs >= threshold members —
        below that the unmask phase could never reconstruct."""
        import time
        r = self._require_round()
        with self._lock:
            members = sorted(set(subset) if subset is not None
                             else set(r.adverts))
            members = [m for m in members if m in r.adverts]
            if len(members) < r.threshold:
                raise SecAggError(
                    f"cannot fix a roster of {len(members)} members below "
                    f"the share threshold t={r.threshold}: the round could "
                    f"never be unmasked")
            r.roster = members
            out = {}
            for m in members:
                out[m] = {
                    "roster": list(members),
                    "pks": {str(i): r.adverts[i]["pk"] for i in members},
                    "shares": {str(i): list(r.adverts[i]["shares"][m])
                               for i in members if m in r.adverts[i]["shares"]},
                }
        if self._agreement_t0 is not None:
            self._h_agreement.observe(time.perf_counter()
                                      - self._agreement_t0)
        return out

    # -- ring fold -----------------------------------------------------------
    def fold(self, silo: int, payload, num_samples: float) -> None:
        """Fold one ADMITTED masked upload at arrival: leafwise uint32
        ring addition into O(model) standing state (the streaming-fold
        seam of `core/stream_agg.py`, in the ring)."""
        r = self._require_round()
        silo = int(silo)
        with self._lock:
            if r.roster is None or silo not in r.roster:
                raise SecAggError(
                    f"masked upload from silo {silo} outside the round's "
                    f"roster {r.roster}")
            if silo in r.folded:
                return  # duplicate delivery already folded
            leaves = [np.asarray(l) for l in _flat_leaves(payload)]
            if r.acc is None:
                r.acc = _rebuild_like(
                    payload, [l.astype(np.uint32, copy=True) for l in leaves])
            else:
                acc_leaves = _flat_leaves(r.acc)
                for a, l in zip(acc_leaves, leaves):
                    a += l.astype(np.uint32)  # in-place ring add
            r.folded[silo] = float(num_samples)
            self._c_masked.inc()

    @property
    def count(self) -> int:
        r = self._round
        return len(r.folded) if r is not None else 0

    @property
    def weight_total(self) -> float:
        """Plaintext sum of the admitted sample counts (ledger / edge
        frame bookkeeping; the AGGREGATION divisor is the masked weight
        sum recovered at finalize)."""
        r = self._round
        return float(sum(r.folded.values())) if r is not None else 0.0

    # -- unmask --------------------------------------------------------------
    def unmask_request(self) -> Tuple[List[int], List[int]]:
        """(survivors, dead): uploaders whose self-masks must be removed,
        and roster members that never uploaded whose stray pairwise
        masks must be reconstructed away."""
        r = self._require_round()
        with self._lock:
            r.unmask_sent = True
            survivors = sorted(r.folded)
            dead = sorted(set(r.roster or []) - set(r.folded))
            return survivors, dead

    def note_reveal(self, silo: int, payload: Dict) -> bool:
        """Bank one survivor's revealed shares; True when every survivor
        has answered (finalize may also proceed earlier once
        `can_finalize`)."""
        r = self._require_round()
        silo = int(silo)
        with self._lock:
            if silo not in r.folded or silo in r.reveals:
                return False
            self._c_share_frames.inc()
            self._c_share_env.inc(len(payload.get("b", {}))
                                  + len(payload.get("sk", {})))
            r.reveals[silo] = {
                "b": {int(k): int(v)
                      for k, v in payload.get("b", {}).items()},
                "sk": {int(k): int(v)
                       for k, v in payload.get("sk", {}).items()},
            }
            return set(r.reveals) >= set(r.folded)

    def can_finalize(self) -> bool:
        r = self._require_round()
        with self._lock:
            return len(r.reveals) >= r.threshold

    def _reconstruct(self, owner: int, kind: str, r: _ServerRound) -> int:
        """Shamir-reconstruct one silo's secret from the revealed shares
        and VERIFY it against the advert's commitment."""
        key = "b" if kind == "self_mask" else "sk"
        pairs = []  # (position in group, share)
        for responder, reveal in r.reveals.items():
            share = reveal[key].get(owner)
            if share is not None:
                pairs.append((r.group.index(responder), share))
        if len(pairs) < r.threshold:
            raise SecAggError(
                f"cannot reconstruct {kind} of silo {owner}: "
                f"{len(pairs)} shares revealed, threshold t={r.threshold} "
                f"— too many dropouts for the configured tolerance")
        pairs = pairs[:r.threshold]
        idx = [p for p, _ in pairs]
        shares = np.asarray([[[s]] for _, s in pairs], np.int64)
        value = int(bgw_decode(shares, idx)[0, 0])
        advert = r.adverts[owner]
        if kind == "self_mask":
            want = advert.get("b_commit")
            got = _commit(value, r.round_idx, owner, "b")
            if want is not None and got != want:
                raise SecAggError(
                    f"self-mask seed of silo {owner} reconstructed to a "
                    f"value that does not match its advert commitment — "
                    f"corrupted or forged shares; refusing to unmask")
        else:
            if pow(GENERATOR, value, _P) != advert["pk"]:
                raise SecAggError(
                    f"pairwise secret of silo {owner} reconstructed to a "
                    f"value whose public key does not match its advert — "
                    f"corrupted or forged shares; refusing to unmask")
        self._c_reconstruct[kind].inc()
        return value

    def finalize(self, reference=None) -> Tuple[object, float]:
        """Remove every residual mask from the ring sum, dequantize, and
        return ``(weighted_mean_tree, recovered_weight_sum)``.

        ``reference``: the round's global params (host tree).  When set,
        the post-unmask defenses run ON THE SUM: the rolling median+MAD
        norm screen over ``||mean − reference||`` (a breached round
        returns ``(None, 0.0)`` and counts
        ``fedml_secagg_sum_rejected_total`` — the global stays put), then
        sum-level norm clipping and weak-DP noise when configured."""
        import time
        t0 = time.perf_counter()
        r = self._require_round()
        with self._lock:
            if not r.folded:
                raise SecAggError("finalize() with no folded uploads")
            survivors = sorted(r.folded)
            dead = sorted(set(r.roster) - set(r.folded))
            acc = r.acc
            acc_leaves = _flat_leaves(acc)
            shapes = [l.shape for l in acc_leaves]
            # survivors' self-masks leave the sum (in place: the acc is
            # server-owned and consumed by this round's finalize)
            for silo in survivors:
                b = self._reconstruct(silo, "self_mask", r)
                _apply_mask_inplace(acc_leaves,
                                    prg_mask(b, r.round_idx, shapes), -1)
            # dead roster members' stray pairwise masks leave the sum:
            # uploader i carried sign_i(j)*PRG(s_ij) for dead j
            for j in dead:
                sk_j = self._reconstruct(j, "pair_key", r)
                for i in survivors:
                    s_ij = pow(r.adverts[i]["pk"], sk_j, _P)
                    sign = 1 if j > i else -1
                    _apply_mask_inplace(
                        acc_leaves, prg_mask(s_ij, r.round_idx, shapes),
                        -sign)
            num = _tree_map_np(lambda l: dequantize_np(l, r.scale),
                               acc["q"])
            den = float(dequantize_np(np.asarray(acc["w"]), r.scale)[0])
            self._c_rounds.inc()
        if den <= 0 or not math.isfinite(den):
            raise SecAggError(
                f"unmasked weight sum {den} is not positive — the ring "
                f"sum wrapped or the unmask removed the wrong masks; "
                f"refusing to publish a corrupted aggregate")
        mean = _tree_map_np(lambda l: (l / den).astype(np.float32), num)
        if reference is not None:
            mean = self._post_unmask_defenses(mean, reference, r.round_idx)
        self._h_unmask.observe(time.perf_counter() - t0)
        return mean, den

    # -- post-unmask sum defenses -------------------------------------------
    def _post_unmask_defenses(self, mean, reference, round_idx: int):
        """The norm screen and defended finalize, on the SUM only (the
        per-upload versions are unavailable by construction under
        masking)."""
        ref_leaves = [np.asarray(l, np.float64)
                      for l in _flat_leaves(reference)]
        mean_leaves = [np.asarray(l, np.float64)
                       for l in _flat_leaves(mean)]
        delta = [m - g for m, g in zip(mean_leaves, ref_leaves)]
        norm = math.sqrt(sum(float(np.sum(d * d)) for d in delta))
        thresh = self._sum_norm_threshold()
        if thresh is not None and norm > thresh:
            self._c_sum_rejected.inc()
            log.warning("secagg round %d: recovered sum norm %.4g beyond "
                        "the rolling screen threshold %.4g — round "
                        "DISCARDED, global unchanged", round_idx, norm,
                        thresh)
            return None
        self._sum_norms.append(norm)
        if self.norm_clip > 0 and norm > self.norm_clip:
            factor = self.norm_clip / norm
            delta = [d * factor for d in delta]
        if self.noise_std > 0:
            key = jax.random.fold_in(jax.random.key(self.seed),
                                     int(round_idx) & 0xFFFFFFFF)
            noisy = []
            for i, d in enumerate(delta):
                k = jax.random.fold_in(key, i)
                noisy.append(d + self.noise_std * np.asarray(
                    jax.random.normal(k, d.shape), np.float64))
            delta = noisy
        if self.norm_clip > 0 or self.noise_std > 0:
            out = [(g + d).astype(np.float32)
                   for g, d in zip(ref_leaves, delta)]
            return _rebuild_like(mean, out)
        return mean

    def _sum_norm_threshold(self) -> Optional[float]:
        if len(self._sum_norms) < self.norm_screen_min_history:
            return None
        arr = np.asarray(self._sum_norms, np.float64)
        med = float(np.median(arr))
        mad = float(np.median(np.abs(arr - med)))
        return med + self.norm_screen_k * max(mad, 0.05 * med, 1e-12)
