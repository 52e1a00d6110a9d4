#!/usr/bin/env python
"""Sustained-degradation survivability soak (ISSUE 19).

Three arms over the same deterministic 6-silo federation (silo 5 is a
NaN-spewing attacker the admission pipeline rejects, silo 6 is
persistently slow):

* **clean** — no chaos, wait policy: the convergence reference;
* **static** — flapping links (drop/dup/delay — never corrupt) on the
  silos 4-6 with the classic drop policy at the static
  ``round_timeout_s`` cap: what degradation costs WITHOUT the spine;
* **degrade** — the same chaos plus a correlated partition cutting
  silos 4 and 6 silo->server (uploads AND heartbeats) over a known
  round span, a mid-soak ``barrier_close`` process kill + in-process
  respawn, and the full degrade spine live: adaptive deadlines,
  quorum-aware closure with partition holds, fault attribution,
  participation debt.

Invariants (any failure exits 1, with the gate named):

  G1  zero network- or unknown-attributed trust strikes — the flaky
      links and deadline drops must NEVER look Byzantine (silo 5's
      payload strikes still land);
  G2  the adaptive deadline undercuts the static cap on >= 80% of warm
      rounds, and round wall-clock tracks it (holds excluded);
  G3  bounded starvation — no honest silo goes more than
      ``STARVE_BOUND`` rounds without an accepted upload;
  G4  the degraded arm's final global lands within ``CONV_TOL`` (L2)
      of the clean arm;
  G5  zero recompiles after warmup under strict sentry on every
      measured arm;
  G6  the killed round's resumed deadline equals the pre-kill one
      exactly — the deadline is a pure function of ledgered history;
  G7  the partition rounds produced >= 1 HOLD (the discrimination
      actually fired), and the kill actually landed.

Determinism: chaos and kills derive from --seed.  ``--smoke`` is the
CI twin (reduced rounds/windows, report labeled smoke=true).

Usage:
  python scripts/degrade_soak.py [--smoke] [--seed N] [--out PATH]
"""

import argparse
import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from fedml_tpu.algorithms.cross_silo import (FailureDetector,  # noqa: E402
                                             FedAvgClientActor,
                                             FedAvgServerActor, MsgType)
from fedml_tpu.comm.chaos import (ChaosPlan, ChaosTransport,  # noqa: E402
                                  LinkChaos, Partition)
from fedml_tpu.comm.local import LocalHub  # noqa: E402
from fedml_tpu.core.stream_agg import StreamingAggregator  # noqa: E402
from fedml_tpu.obs.perf import PerfRecorder  # noqa: E402
from fedml_tpu.obs.trend import load_ledger  # noqa: E402
from fedml_tpu.robust import AdmissionPipeline, TrustTracker  # noqa: E402
from fedml_tpu.robust.degrade import ReliabilityTracker  # noqa: E402
from fedml_tpu.robust.faultline import (ActorKilled, CrashSpec,  # noqa: E402
                                        Faultline)
from fedml_tpu.utils.checkpoint import RoundCheckpointer  # noqa: E402
from fedml_tpu.utils.journal import RoundJournal  # noqa: E402

MAX_RESPAWNS = 5
N_SILOS = 6
ATTACKER = 5          # NaN upload every tasked round: payload strikes
SLOW = 6              # persistently slow but honest: must never strike
HONEST = (1, 2, 3, 4, SLOW)
FLAKY = (4, 5, 6)     # silos on bad links; 1-3 stay clean so the
#                       quorum floor of 3 is always reachable (liveness)
PARTITIONED = (4, 6)  # the correlated window cuts these silo->server
WARMUP_ROUNDS = 5
STARVE_BOUND = 6
CONV_TOL = 1.5
FRAC_THRESHOLD = 0.8
WALL_SLACK_S = 0.5


class Violation(Exception):
    pass


def _cfg(smoke):
    # the partition is ROUND-bounded (cut rounds in [a, b)), not
    # wall-clock: a cold-start stall on a chaos-dropped upload can eat
    # seconds, and a wall window would drift past the rounds it meant
    # to hit; round space is immune to that variance.  Two partition
    # rounds, not one — the hold needs EVERY missing silo non-ALIVE,
    # and a coincidental chaos drop of the (beating, alive) attacker's
    # upload in one round spoils that evidence; two rounds make the
    # spoiler a coincidence squared.
    if smoke:
        return dict(rounds=10, static_rounds=4, cap=3.0, slow_s=0.4,
                    part=(6, 8), kill_round=8, suspect_s=0.5)
    return dict(rounds=40, static_rounds=12, cap=5.0, slow_s=0.8,
                part=(12, 14), kill_round=18, suspect_s=0.75)


def _params(seed=3):
    rng = np.random.RandomState(seed)
    return {"dense": {"kernel": rng.randn(6, 4).astype(np.float32),
                      "bias": rng.randn(4).astype(np.float32)}}


def _train_fn(silo, slow_s=0.0):
    """Deterministic per (silo, round) — identical params across arms;
    only the LATENCY differs (the slow silo sleeps, the attacker
    spews NaN)."""
    def fn(params, client_idx, round_idx):
        if silo == SLOW and slow_s > 0:
            time.sleep(slow_s)
        if silo == ATTACKER:
            return jax.tree.map(
                lambda v: np.full_like(np.asarray(v), np.nan), params), 10
        rng = np.random.RandomState(1000 * silo + int(round_idx or 0))
        return jax.tree.map(
            lambda v: v + rng.randn(*np.shape(v)).astype(np.float32) * 0.1,
            params), 10 + silo
    return fn


def _l2(a, b):
    return float(np.sqrt(sum(
        float(np.sum((np.asarray(x, np.float64)
                      - np.asarray(y, np.float64)) ** 2))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))))


def _plan(seed, part=None):
    """Flapping links for silos 4-6 (both directions, never corrupt —
    every payload strike must trace to the attacker), plus the
    correlated round-bounded partition cutting silos 4 and 6
    silo->server (uploads AND round-tagged heartbeats: the detector
    evidence the verdict needs)."""
    flaky = dict(drop_prob=0.08, dup_prob=0.05, delay_prob=0.2,
                 max_delay_s=0.05)
    links = {}
    for s in FLAKY:
        links[(s, 0)] = LinkChaos(**flaky)
        links[(0, s)] = LinkChaos(**flaky)
    if part is not None:
        for s in PARTITIONED:
            links[(s, 0)] = LinkChaos(
                partition=Partition(after_round=part[0],
                                    until_round=part[1]), **flaky)
    return ChaosPlan(seed=seed, default=LinkChaos(), links=links,
                     immune_types=(MsgType.S2C_FINISH,
                                   MsgType.ROUND_TIMEOUT))


def _compose_extra(named):
    """Named (get, set) pairs folded into one extra_state hook (the
    main.py composition, inlined so the soak never imports the CLI)."""
    def get():
        return {name: g() for name, (g, _) in named}

    def set_(tree):
        for name, (_, s) in named:
            sub = tree.get(name) if hasattr(tree, "get") else None
            if sub is not None:
                s(sub)
    return (get, set_)


def _run(workdir, *, rounds, plan=None, cap=None, slow_s=0.0,
         degrade_cfg=None, suspect_s=None, fl=None, perf_path=None,
         ck=False, hb_s=None, deadline_trace=None):
    """One federation attempt: pump when chaos-free, threaded drive
    under a ChaosTransport wrap.  Returns (server, admission)."""
    init = _params(3)
    hub = LocalHub(codec_roundtrip=True)
    wrap = (lambda t: t) if plan is None \
        else (lambda t: ChaosTransport(t, plan))
    perf = None
    if perf_path:
        perf = PerfRecorder(perf_path, strict_recompiles=True,
                            rss_interval_s=10.0)
    stream = StreamingAggregator(init, method="mean", kind="params",
                                 norm_clip=1.0, seed=0,
                                 sentry=perf.sentry if perf else None)
    adm = AdmissionPipeline(
        init, kind="params",
        trust=TrustTracker(strikes_to_quarantine=1, quarantine_rounds=5,
                           probation_rounds=2))
    extra = (lambda: adm.trust.state_dict(N_SILOS),
             adm.trust.load_state_dict)
    degrade = None
    if degrade_cfg is not None:
        degrade = ReliabilityTracker(N_SILOS, **degrade_cfg)
        if deadline_trace is not None:
            orig = degrade.deadline_s

            def spy(expected, cap_s, _orig=orig, _t=deadline_trace):
                d = _orig(expected, cap_s)
                _t.append(d)
                return d
            degrade.deadline_s = spy
        extra = _compose_extra([
            ("trust", extra),
            ("degrade", (degrade.state_dict, degrade.load_state_dict))])
    kw = {}
    if cap is not None:
        kw = dict(straggler_policy="drop", round_timeout_s=cap,
                  min_silo_frac=0.5)
    if suspect_s is not None:
        # dead_after_s huge: partitioned silos go SUSPECT, never DEAD —
        # the spine must survive on suspicion evidence alone
        kw["failure_detector"] = FailureDetector(
            suspect_after_s=suspect_s, dead_after_s=3600.0)
    server = FedAvgServerActor(
        wrap(hub.transport(0)), init, N_SILOS, N_SILOS, rounds,
        checkpointer=(RoundCheckpointer(os.path.join(workdir, "ck"),
                                        save_every=1) if ck else None),
        journal=(RoundJournal(os.path.join(workdir, "j"),
                              snapshot_every=1) if ck else None),
        stream_agg=stream, admission=adm, extra_state=extra,
        degrade=degrade, faultline=fl, perf=perf, **kw)
    silos = [FedAvgClientActor(i, wrap(hub.transport(i)),
                               _train_fn(i, slow_s=slow_s),
                               heartbeat_interval_s=hb_s)
             for i in range(1, N_SILOS + 1)]
    server.register_handlers()
    for s in silos:
        s.register_handlers()
    try:
        if plan is not None:
            import threading
            threads = [threading.Thread(target=a.run, daemon=True)
                       for a in silos]
            for t in threads:
                t.start()
            server.start()
            server.transport.run()
            for t in threads:
                t.join(timeout=10)
        else:
            server.start()
            hub.pump()
    finally:
        if perf is not None:
            perf.close()
    return server, adm


def _merged_rows(perf_paths):
    """Per-round ledger rows across respawn attempts (a later attempt's
    re-run of a round wins); each attempt's first row is flagged — it
    pays the jit compiles and is excluded from wall tracking."""
    rows = {}
    for path in perf_paths:
        if not os.path.exists(path):
            continue
        for i, r in enumerate(load_ledger(path)):
            r = dict(r)
            r["_attempt_first"] = (i == 0)
            rows[int(r["round"])] = r
    return [rows[k] for k in sorted(rows)]


def _recompiles_after_warmup(perf_paths):
    total = 0
    for path in perf_paths:
        if not os.path.exists(path):
            continue
        rows = load_ledger(path)
        total += sum(int(r.get("recompiles") or 0) for r in rows[1:])
    return total


def _starvation(bench_rows):
    """Max consecutive rounds each honest silo went unfolded, from the
    per-round accepted sets on the degrade ledger."""
    worst = {}
    for silo in HONEST:
        since = mx = 0
        for row in bench_rows:
            if silo in row["accepted_silos"]:
                since = 0
            else:
                since += 1
            mx = max(mx, since)
        worst[str(silo)] = mx
    return worst


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced CI twin (artifact labeled smoke=true)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=str, default="",
                    help="write the JSON report here")
    args = ap.parse_args(argv)
    cfg = _cfg(args.smoke)
    backend = jax.default_backend()

    # -- clean arm: the convergence reference ---------------------------
    print("[degrade_soak] arm clean ...", flush=True)
    with tempfile.TemporaryDirectory() as d:
        clean_srv, _ = _run(d, rounds=cfg["rounds"])
        clean_params, clean_rounds = clean_srv.params, clean_srv.round_idx

    # -- static arm: drop policy at the static cap ----------------------
    print("[degrade_soak] arm static ...", flush=True)
    with tempfile.TemporaryDirectory() as d:
        pp = os.path.join(d, "perf.jsonl")
        static_srv, _ = _run(d, rounds=cfg["static_rounds"],
                             plan=_plan(args.seed), cap=cfg["cap"],
                             slow_s=cfg["slow_s"], perf_path=pp)
        static_rows = [{"round": int(r["round"]),
                        "wall_s": round(float(r["round_s"]), 4)}
                       for r in _merged_rows([pp])]
        static_rc = _recompiles_after_warmup([pp])
        static_rounds_done = static_srv.round_idx

    # -- degrade arm: the spine under chaos + partition + kill ----------
    print("[degrade_soak] arm degrade ...", flush=True)
    degrade_cfg = dict(min_quorum=0.5, adaptive_deadline=True,
                       deadline_floor_s=0.3, deadline_quantile=0.9,
                       deadline_slack=1.5, partition_frac=0.3,
                       partition_max_holds=3, min_history=2)
    traces, perfs, failures = {}, [], []
    with tempfile.TemporaryDirectory() as d:

        def once(fl, attempt):
            trace = traces.setdefault(attempt, [])
            pp = os.path.join(d, f"a{attempt}-perf.jsonl")
            perfs.append(pp)
            # round-bounded partition: by the resumed round (>= the
            # kill round, past the partition span) the cut is inert,
            # so every attempt safely runs the SAME plan
            plan = _plan(args.seed, part=cfg["part"])
            return _run(d, rounds=cfg["rounds"], plan=plan,
                        cap=cfg["cap"], slow_s=cfg["slow_s"],
                        degrade_cfg=degrade_cfg,
                        suspect_s=cfg["suspect_s"], fl=fl, perf_path=pp,
                        ck=True, hb_s=0.25, deadline_trace=trace)

        fl = Faultline(crashes=[CrashSpec(point="barrier_close", hit=1,
                                          round_idx=cfg["kill_round"])],
                       seed=args.seed)
        for attempt in range(MAX_RESPAWNS + 1):
            try:
                deg_srv, deg_adm = once(fl, attempt)
                break
            except ActorKilled:
                fl.respawn()
        else:
            raise Violation(f"still crashing after {MAX_RESPAWNS} "
                            f"respawns")

        rows = _merged_rows(perfs)
        bench_rows = []
        for r in rows:
            dg = r.get("degrade") or {}
            bench_rows.append({
                "round": int(r["round"]),
                "wall_s": round(float(r["round_s"]), 4),
                "deadline_s": dg.get("deadline_s"),
                "accepted_silos": dg.get("accepted") or [],
                "accepted": len(dg.get("accepted") or []),
                "dropped": len(dg.get("dropped") or []),
                "holds": int(dg.get("holds") or 0),
                "attempt_first": bool(r.get("_attempt_first"))})
        deg_rc = _recompiles_after_warmup(perfs)
        sft = deg_adm.trust.strike_fault_totals()
        starve = _starvation(bench_rows)
        tracker = deg_srv.degrade

    # -- gates ----------------------------------------------------------
    warm = [r for r in bench_rows if r["round"] >= WARMUP_ROUNDS
            and isinstance(r["deadline_s"], (int, float))]
    under = sum(1 for r in warm if r["deadline_s"] < cfg["cap"])
    beat_frac = under / len(warm) if warm else 0.0
    nohold = [r for r in warm
              if not r["holds"] and not r["attempt_first"]]
    tracked = sum(1 for r in nohold
                  if r["wall_s"] <= r["deadline_s"] + WALL_SLACK_S)
    track_frac = tracked / len(nohold) if nohold else 0.0
    pre = traces.get(0, [None])[-1]
    post = traces.get(1, [None])[0]
    delta = _l2(deg_srv.params, clean_params)
    gates = {
        "zero_network_strikes": {
            "ok": sft.get("network", 0) == 0 and sft.get("unknown", 0) == 0,
            "network": sft.get("network", 0),
            "unknown": sft.get("unknown", 0)},
        "payload_strikes_land": {
            "ok": sft.get("payload", 0) >= 1, "payload": sft.get("payload", 0)},
        "adaptive_beats_static": {
            "ok": beat_frac >= FRAC_THRESHOLD, "frac": round(beat_frac, 3),
            "threshold": FRAC_THRESHOLD, "warm_rounds": len(warm)},
        "deadline_tracks_wall": {
            "ok": track_frac >= FRAC_THRESHOLD,
            "frac": round(track_frac, 3), "threshold": FRAC_THRESHOLD,
            "slack_s": WALL_SLACK_S, "rounds": len(nohold)},
        "bounded_starvation": {
            "ok": all(v <= STARVE_BOUND for v in starve.values()),
            "bound": STARVE_BOUND, "worst": max(starve.values())},
        "convergence_vs_clean": {
            "ok": delta <= CONV_TOL, "delta": round(delta, 4),
            "tolerance": CONV_TOL},
        "zero_recompiles": {
            "ok": static_rc == 0 and deg_rc == 0,
            "static": static_rc, "degrade": deg_rc},
        "resume_deadline_determinism": {
            "ok": (isinstance(pre, float) and isinstance(post, float)
                   and abs(pre - post) < 1e-9 and pre < cfg["cap"]),
            "pre": pre, "post": post},
        "partition_hold_exercised": {
            "ok": tracker.holds_total >= 1 and fl.kills >= 1,
            "holds": tracker.holds_total, "kills": fl.kills},
        "bounded_progress": {
            "ok": (deg_srv.round_idx == cfg["rounds"]
                   and clean_rounds == cfg["rounds"]
                   and static_rounds_done == cfg["static_rounds"]),
            "degrade_rounds": deg_srv.round_idx},
    }
    failures = [f"{name}: {v}" for name, v in gates.items() if not v["ok"]]

    bench = {
        "bench": "degrade", "version": 1, "smoke": bool(args.smoke),
        "seed": args.seed, "backend": backend, "n_silos": N_SILOS,
        "attacker_silo": ATTACKER, "slow_silo": SLOW,
        "rounds": cfg["rounds"], "round_timeout_s": cfg["cap"],
        "warmup_rounds": WARMUP_ROUNDS,
        "partition_rounds": list(cfg["part"]),
        "degrade_config": degrade_cfg,
        "arms": {
            "clean": {"backend": backend,
                      "rounds_completed": clean_rounds},
            "static": {"backend": backend,
                       "rounds_completed": static_rounds_done,
                       "rounds": static_rows,
                       "wall_p90_s": round(float(np.percentile(
                           [r["wall_s"] for r in static_rows], 90)), 4),
                       "recompiles_after_warmup": static_rc},
            "degrade": {
                "backend": backend,
                "rounds_completed": deg_srv.round_idx,
                "rounds": [{k: v for k, v in r.items()
                            if k != "attempt_first"}
                           for r in bench_rows],
                "wall_p90_s": round(float(np.percentile(
                    [r["wall_s"] for r in bench_rows], 90)), 4),
                "strike_fault_totals": sft,
                "max_rounds_since_accept": starve,
                "holds_total": tracker.holds_total,
                "drops_total": tracker.drops_total,
                "kill_round": cfg["kill_round"], "kills": fl.kills,
                "resume": {"round": cfg["kill_round"],
                           "deadline_pre_kill": pre,
                           "deadline_post_resume": post},
                "final_delta_vs_clean": round(delta, 4),
                "recompiles_after_warmup": deg_rc},
        },
        "gates": gates,
    }
    print(json.dumps(bench["gates"], indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(bench, f, indent=2)
        print(f"[degrade_soak] wrote {args.out}")
    if failures:
        for f in failures:
            print(f"[degrade_soak] GATE FAILED {f}", file=sys.stderr)
        return 1
    print(f"[degrade_soak] all {len(gates)} gates green "
          f"(delta vs clean {delta:.3f}, holds {tracker.holds_total}, "
          f"strikes {sft})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
