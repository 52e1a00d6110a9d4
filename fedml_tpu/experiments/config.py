"""The unified experiment config — one typed tree replacing the reference's
per-entry argparse soup (``fedml_experiments/distributed/fedavg/
main_fedavg.py:46-112``) plus its launch satellites (``gpu_mapping.yaml``,
``mpi_host_file``, ``grpc_ipconfig.csv``).

Flag parity: every behavioral flag of the reference's ``add_args`` exists
here under the same name (model, dataset, data_dir, partition_method,
partition_alpha, client_num_in_total, client_num_per_round, batch_size,
client_optimizer, lr, wd, epochs, comm_round, frequency_of_the_test, ci).
GPU placement flags (gpu_server_num / gpu_num_per_server / gpu_mapping_*)
are replaced by mesh flags (``--mesh_clients``), and ``mpirun -np N
-hostfile`` is replaced by ``--coordinator_address/--num_processes/
--process_id`` feeding ``jax.distributed.initialize``
(fedml_tpu/parallel/mesh.py).
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Optional


@dataclasses.dataclass
class ExperimentConfig:
    # ---- reference argparse parity (main_fedavg.py:46-112) -------------
    algo: str = "fedavg"
    model: str = "lr"
    dataset: str = "mnist"
    data_dir: Optional[str] = None       # None => hermetic synthetic twin
    partition_method: str = "hetero"
    partition_alpha: float = 0.5
    client_num_in_total: int = 1000
    client_num_per_round: int = 10
    batch_size: int = 10
    client_optimizer: str = "sgd"
    compute_dtype: str = ""              # "bfloat16": MXU mixed precision
    lr: float = 0.03
    wd: float = 0.001
    epochs: int = 1
    comm_round: int = 10
    frequency_of_the_test: int = 5
    rounds_per_dispatch: int = 1         # >1: lax.scan K rounds per dispatch
    ci: int = 0                          # short-circuit eval (CI mode flag)
    seed: int = 0

    # ---- server optimizer (FedOpt, fedopt/optrepo.py registry) ---------
    server_optimizer: str = "sgd"
    server_lr: float = 1.0
    server_momentum: float = 0.9

    # ---- server-optimizer spine (fedml_tpu/server_opt, ISSUE 18) -------
    server_opt: str = "plain"         # LIVE server step over the
    #                                   streaming/sharded finalize:
    #                                   plain (bit-identical pre-seam
    #                                   assignment) | momentum | adam |
    #                                   fedac — the finalize output
    #                                   becomes a pseudo-gradient and
    #                                   the optimizer's one jitted step
    #                                   applies it (lr/momentum ride
    #                                   --server_lr/--server_momentum;
    #                                   fedac knobs ride --fedac_*)
    server_adam_beta1: float = 0.9    # server_opt adam first moment
    server_adam_beta2: float = 0.999  # server_opt adam second moment
    server_adam_eps: float = 1e-8     # server_opt adam denominator floor
    adaptive: bool = False            # health-driven adaptive round
    #                                   controller (server_opt/
    #                                   controller.py): steer cohort /
    #                                   epochs / wave pacing from the
    #                                   PR 8 drift alarms; every decision
    #                                   named on the perf-ledger line.
    #                                   Requires --health
    adapt_min_cohort: int = 2         # adaptive: cohort backoff floor
    adapt_patience: int = 2           # adaptive: calm rounds before
    #                                   levers decay back to baseline

    # ---- algorithm extras ----------------------------------------------
    mu: float = 0.1                      # FedProx proximal term
    ditto_lambda: float = 0.1            # Ditto: personalization pull λ
    personal_lr: float = 0.0             # Ditto: 0 → inherit --lr
    personal_epochs: int = 0             # Ditto: 0 → inherit --epochs
    feddyn_alpha: float = 0.01           # FedDyn: dynamic-reg strength α
    fedac_mu: float = 0.0                # FedAC: >0 derives (γ,α,β)
    fedac_gamma: float = 0.0             # FedAC explicit knobs (0 → lr)
    fedac_alpha: float = 1.0
    fedac_beta: float = 1.0
    dp_clip: float = 1.0                 # dp_fedavg: per-user L2 bound S
    dp_noise_multiplier: float = 1.0     # dp_fedavg: z (std = S·z/m)
    dp_delta: float = 1e-5               # dp_fedavg: δ for reported ε
    dp_accounting: str = "fixed_size"    # dp_fedavg: fixed_size | poisson
    gmf: float = 0.0                     # FedNova global momentum factor
    norm_bound: float = 5.0              # robust: clip threshold
    stddev: float = 0.025                # robust: weak-DP noise
    defense: str = "weak_dp"             # robust: clip/weak_dp/none or a
    #                                      Byzantine rule (coordinate_median,
    #                                      trimmed_mean, krum, multi_krum,
    #                                      geometric_median)
    trim_frac: float = 0.1               # trimmed_mean: cut per side
    byz_f: int = 0                       # krum: assumed Byzantine count
    krum_m: int = 1                      # multi_krum: updates averaged
    gm_iters: int = 8                    # geometric_median: Weiszfeld steps
    gm_eps: float = 1e-6                 # geometric_median: smoothing floor
    defense_backend: str = "xla"         # robust: "xla" | "pallas" (fused
    #                                      clip+noise+mean, core/pallas_agg)
    # robust: backdoor attack evaluation (poison_type pipeline,
    # FedAvgRobustAggregator.py:14-45, 270)
    backdoor: bool = False               # poison attacker shards + eval
    attacker_num: int = 1                # first K clients are attackers
    target_label: int = 9                # attack target ("truck" for cifar)
    poison_frac: float = 1.0             # fraction of attacker shard stamped
    trigger_size: int = 3                # pixel-trigger side length
    group_num: int = 2                   # hierarchical / turboaggregate
    group_comm_round: int = 2            # hierarchical
    drop_tolerance: int = 1              # turboaggregate
    secagg_backend: str = "xla"          # turboaggregate: "xla" | "pallas"
    neighbor_num: int = 2                # decentralized topology
    # cross-silo actor mode (distributed FedAvg over host transports;
    # reference: run_fedavg_distributed_pytorch.sh + grpc_ipconfig.csv)
    silo_backend: str = "local"          # "local" (in-process hub) | "grpc"
    node_id: int = 0                     # grpc: 0=server, 1..N=silos
    ip_config: str = ""                  # grpc: rank→IP csv (reference fmt)
    base_port: int = 50000               # grpc: port = base_port + node_id
    grpc_max_message_mb: int = 1000      # grpc: per-message size cap (sends
    #                                      warn loudly at 80% of it instead
    #                                      of a bare RESOURCE_EXHAUSTED)
    grpc_workers: int = 4                # grpc: inbound RPC thread pool —
    #                                      raise with the cohort on the
    #                                      server node
    straggler_policy: str = "wait"       # wait | drop | abort
    round_timeout_s: float = 0.0         # 0 = no straggler timer
    min_silo_frac: float = 0.5           # drop-policy quorum
    # decentralized online learning (standalone/decentralized main_dol.py)
    mode: str = "DOL"                    # "DOL" | "PUSHSUM" | "LOCAL"
    iteration_number: int = 100          # stream length T per client
    beta: float = 0.0                    # adversarial (kmeans) stream frac
    b_symmetric: bool = False            # undirected vs directed topology
    topology_neighbors_num_undirected: int = 4
    topology_neighbors_num_directed: int = 4
    time_varying: bool = False           # regenerate graph each iteration
    temperature: float = 3.0             # FedGKT KD temperature
    lambda_l1: float = 0.0               # AsDGan G reconstruction L1 term
    lambda_perceptual: float = 0.0       # AsDGan G VGG-feature term
    fednas_layers: int = 3               # DARTS search depth
    fednas_channels: int = 8             # DARTS init channels
    fednas_steps: int = 2                # DARTS cell steps

    # ---- TPU placement (replaces gpu_mapping / mpirun) -----------------
    mesh_clients: int = 0     # >0: shard the cohort over this many devices
    mesh_groups: int = 0      # >0 (hierarchical): [groups, clients] mesh
    mesh_sequence: int = 0    # >0 (fedavg + transformer): dp x sp
    #                           [clients, sequence] mesh with ring attention
    mesh_stages: int = 0      # >0 (cross_silo + transformer): silo-local
    #                           pipeline parallelism — transformer blocks
    #                           over this many stage devices (GPipe,
    #                           parallel/pipeline.py); composes with
    #                           --moe_experts (balance loss rides the
    #                           schedule's scan carry)
    pp_microbatches: int = 0  # GPipe microbatches (0 = mesh_stages)
    eval_chunk_clients: int = 1024  # evaluate_global clients per compiled
    #                                 call; bounds eval memory on large
    #                                 corpora (0 = one-shot vmap)
    attn_block_size: int = 0  # >0 (transformer): flash-style kv blocking —
    #                           O(T*block) attention memory for single-chip
    #                           train/eval at long context
    attn_flash: bool = False  # transformer: TPU pallas flash-attention
    #                           kernel (fails loudly off-TPU)
    moe_experts: int = 0      # >0 (transformer): Switch MoE FFN with this
    #                           many experts (models/moe.py); expert tables
    #                           are ep-shardable (parallel/expert.py)
    model_config: str = ""    # (transformer) a JSON file of a published
    #                           architecture's keys under their own names,
    #                           built as the arch its model_type names
    #                           (experiments/models.arch_of: latent
    #                           attention with routed + shared experts,
    #                           indexer-selected grouped-query attention,
    #                           or window and full grouped-query layers),
    #                           plus the share of a layer
    #                           held here: experts_held, first_held,
    #                           vocab_held
    silo_idle_timeout_s: float = 0.0  # grpc silos: exit after this long
    #                                   with no traffic (0 = wait forever)
    # ---- fault tolerance (comm/resilient.py + cross_silo health) -------
    heartbeat_s: float = 0.0          # >0: silos send liveness beats at
    #                                   this interval (threaded/grpc modes)
    dead_after_s: float = 0.0         # >0: server failure detector — silos
    #                                   unheard this long are DEAD and
    #                                   excluded from the round quorum
    suspect_after_s: float = 0.0      # detector SUSPECT threshold
    #                                   (0 = dead_after_s / 2)
    retask_timeout_s: float = 0.0     # async_fl: re-task silos quiet this
    #                                   long (liveness under upload loss)
    silo_retries: int = 0             # >0: wrap the wire transport in
    #                                   ResilientTransport with this many
    #                                   send attempts (backoff + jitter +
    #                                   reconnect between attempts)
    # ---- sustained degradation (fedml_tpu/robust/degrade.py, ISSUE 19) -
    min_quorum: float = 0.0           # >0: quorum-aware closure — the
    #                                   deadline may close the round only
    #                                   once ceil(frac*expected) silos
    #                                   folded (raises the drop-policy
    #                                   quorum, never lowers it); needs
    #                                   --straggler_policy drop
    adaptive_deadline: bool = False   # arm the straggler timer from the
    #                                   observed per-silo completion
    #                                   quantile (p90 * slack) instead of
    #                                   the static --round_timeout_s
    #                                   (which stays the ceiling and the
    #                                   cold-start fallback)
    deadline_floor_s: float = 0.5     # adaptive deadline lower clamp
    deadline_quantile: float = 0.9    # completion quantile the deadline
    #                                   derives from
    deadline_slack: float = 1.5       # deadline = quantile * slack
    partition_frac: float = 0.0       # >0: a deadline miss of at least
    #                                   this cohort fraction WITH network
    #                                   evidence (dead-letters / detector
    #                                   suspects) HOLDS the round instead
    #                                   of folding a minority view
    partition_max_holds: int = 3      # holds before the round abandons
    #                                   loudly (global unchanged)
    wire_compression: str = "none"    # cross_silo uploads: none|topk|int8
    topk_frac: float = 0.1            # topk: fraction of entries kept
    error_feedback: bool = False      # carry the compression residual into
    #                                   the next round's delta (EF-SGD style;
    #                                   silo-local state, so gRPC silos must
    #                                   be persistent processes — they are)
    # ---- payload defense (fedml_tpu/robust: admission + defended agg) --
    robust_agg: str = "mean"          # cross_silo/async_fl LIVE aggregation
    #                                   rule: mean | coordinate_median |
    #                                   trimmed_mean | krum | multi_krum |
    #                                   geometric_median (rule knobs ride
    #                                   --trim_frac/--byz_f/--krum_m/
    #                                   --gm_iters/--gm_eps)
    norm_clip: float = 0.0            # >0: clip each upload's update norm
    #                                   (reference RobustAggregator parity)
    agg_noise_std: float = 0.0        # >0: weak-DP noise on the defended
    #                                   aggregate (reference parity)
    admission: str = "auto"           # upload admission screen: auto (on
    #                                   whenever any defense flag is set,
    #                                   or under --chaos_corrupt — an
    #                                   unscreened corrupted frame can
    #                                   crash the decoder) | on | off
    max_num_samples: float = 1e6      # admission: cap on the self-reported
    #                                   sample count (0 = uncapped)
    norm_screen_k: float = 6.0        # admission: reject norms beyond
    #                                   median + k * MAD of recent accepts
    norm_screen_window: int = 64      # admission: rolling norm history
    norm_screen_min_history: int = 8  # admission: norms banked before the
    #                                   outlier screen arms
    strikes_to_quarantine: int = 3    # TrustTracker: strikes before
    #                                   quarantine
    quarantine_rounds: int = 4        # TrustTracker: rounds served before
    #                                   probation
    probation_rounds: int = 2         # TrustTracker: clean rounds to
    #                                   restore full trust
    # ---- streaming aggregation (core/stream_agg.py, ROADMAP item 2) ----
    agg_mode: str = "stack"           # cross_silo/async_fl aggregation
    #                                   memory regime: stack (the
    #                                   [cohort,...] staged buffer — exact
    #                                   reference semantics, RSS linear in
    #                                   cohort) | stream (fold each
    #                                   admitted upload at arrival —
    #                                   O(model) state, RSS flat in
    #                                   cohort; mean is bit-identical to
    #                                   stack's DEFENDED-mean path; an
    #                                   undefended stack run differs in
    #                                   last-ulp summation order (sync)
    #                                   or per-delta staleness discounts
    #                                   (async) — README "Streaming
    #                                   aggregation"; robust rules see a
    #                                   bounded reservoir sample)
    stream_reservoir: int = 64        # stream + a robust rule: reservoir
    #                                   slots the rule sees at finalize
    #                                   (size to the adversary count, not
    #                                   the cohort; exact when cohort<=K)
    # ---- sharded global-model spine (fedml_tpu/shard_spine) ------------
    model_shards: int = 0             # >0 (cross_silo + --agg_mode
    #                                   stream): lay the global model
    #                                   out as S shards — broadcast and
    #                                   uploads ship per-shard slices
    #                                   (one encode per shard, screened
    #                                   per shard), the streaming fold
    #                                   state itself is sharded (each
    #                                   shard's accumulator is
    #                                   O(model/S), on its own device
    #                                   when >= S devices exist), and
    #                                   the defended finalize runs per
    #                                   shard.  1 = the sharded
    #                                   machinery with one shard
    #                                   (bit-identical to the
    #                                   replicated path — the parity
    #                                   pin); 0 = off
    fused_finalize: str = "auto"      # shard finalize backend: auto
    #                                   (fused Pallas kernel on TPU,
    #                                   XLA compose on CPU) | on (force
    #                                   the kernel; interpret mode off-
    #                                   TPU — the parity/proof mode) |
    #                                   off (XLA compose everywhere).
    #                                   One kernel launch per shard:
    #                                   division + weak-DP noise fused
    #                                   (sigma=0 bit-identical to XLA
    #                                   for f32 models).  Requires
    #                                   --model_shards >= 1
    edge_aggregators: int = 0         # >0: multi-level topology — this
    #                                   many EdgeAggregatorActor tiers
    #                                   between silos and the root; each
    #                                   edge folds its silos locally and
    #                                   ships ONE pre-reduced update per
    #                                   round (cross_silo local backend)
    # ---- zero-copy pipelined ingest (comm/ingest.py, ISSUE 20) ---------
    ingest_pipeline: bool = False     # opt-in receive path: the
    #                                   transport thread only validates
    #                                   frame headers and enqueues; one
    #                                   fold worker per shard runs
    #                                   decode → screen → fold in
    #                                   arrival order (bit-identical to
    #                                   the inline path).  cross_silo /
    #                                   async_fl servers and the
    #                                   cross_device wave loop; requires
    #                                   --agg_mode stream on the actor
    #                                   paths and refuses unproven
    #                                   combinations loudly (--wire_
    #                                   compression, grpc backend,
    #                                   --edge_aggregators, faultline)
    ingest_queue_depth: int = 64      # bounded per-shard ingest queue
    #                                   depth; overflow dead-letters
    #                                   through the degradation fault
    #                                   feed as a NETWORK fault — never
    #                                   a trust strike, never silent
    # ---- secure aggregation (secure/protocol.py, ROADMAP item 3) -------
    secagg: str = "off"               # cross_silo live secure aggregation:
    #                                   off | pairwise (one masking group =
    #                                   the whole cohort) | grouped
    #                                   (masking scoped per edge block —
    #                                   requires --edge_aggregators;
    #                                   TurboAggregate's grouped scheme,
    #                                   mask-agreement traffic O(N^2/E)).
    #                                   Uploads are quantized into the
    #                                   uint32 ring and pairwise+self
    #                                   masked; the server learns only the
    #                                   cohort sum.  Dropouts recover via
    #                                   t-of-N Shamir shares (unmask phase
    #                                   at barrier close).  Requires
    #                                   --agg_mode stream (the masked fold
    #                                   is ring addition at arrival; there
    #                                   is no stack path).
    secagg_threshold: int = 0         # t of t-of-N Shamir: shares needed
    #                                   to reconstruct a seed — the round
    #                                   survives up to N-t dropouts and
    #                                   fails LOUDLY beyond.  0 = majority
    #                                   (N//2+1, min 2)
    secagg_clip: float = 64.0         # per-coordinate clip before ring
    #                                   quantization; the fixed-point
    #                                   scale auto-derives from the group
    #                                   size so the cohort sum cannot
    #                                   wrap uint32
    adversary: str = ""               # seeded per-silo attacks over the
    #                                   real message path, e.g.
    #                                   "2:scale:20,3:sign_flip" (kinds:
    #                                   sign_flip scale gauss nan_bomb
    #                                   inflate backdoor)
    # ---- cross-device mega-cohort engine (algorithms/cross_device.py) --
    cross_device: bool = False        # train the round as compiled client
    #                                   WAVES (vmap single-chip, shard_map
    #                                   on a --mesh_clients mesh) with each
    #                                   wave's stacked updates folded
    #                                   device-side into the streaming
    #                                   spine at wave completion — 1k-100k
    #                                   sampled clients per round at
    #                                   O(model) server memory.  Shorthand
    #                                   for --algo cross_device (both
    #                                   spellings work; combining it with
    #                                   any other --algo fails loudly)
    wave_size: int = 0                # clients per compiled wave (static
    #                                   shape; last wave pads with
    #                                   weight-0 slots).  0 = auto:
    #                                   min(cohort, 256) rounded up to a
    #                                   mesh-axis multiple
    local_alg: str = "sgd"            # per-client trainer inside the
    #                                   compiled wave: sgd | fedprox
    #                                   (--mu) | scaffold (host-stacked
    #                                   control variates) | fednova
    #                                   (normalized averaging)
    sampler: str = "numpy"            # cross_device cohort sampler:
    #                                   numpy (reference-bit-exact
    #                                   RandomState chain — the baseline-
    #                                   comparable default) | jax (on-
    #                                   device permutation).  THE TWO
    #                                   DIVERGE; the choice is recorded
    #                                   in every metrics.jsonl row so
    #                                   curves are never silently
    #                                   cross-compared
    async_goal: int = 0               # async_fl: aggregate every K uploads
    #                                   (0 = n_silos // 2, FedBuff style)
    staleness_exponent: float = 0.5   # async_fl: (1+s)^-alpha discount
    async_server_lr: float = 1.0      # async_fl: server step on the mean
    completion_signal: str = ""       # write the final summary line here on
    #                                   completion (FIFO or file; parity with
    #                                   the reference's ./tmp/fedml pipe)
    platform: Optional[str] = None       # force jax platform (e.g. "cpu")
    host_device_count: int = 0           # virtual CPU devices (simulation)
    coordinator_address: Optional[str] = None  # multi-host bootstrap
    num_processes: int = 1
    process_id: int = 0

    # ---- observability (obs/ subsystem) --------------------------------
    run_dir: Optional[str] = None        # metrics.jsonl + summary.json here
    metrics_dir: Optional[str] = None    # alias for --run_dir (obs naming;
    #                                      wins when both are given)
    profile_dir: Optional[str] = None    # jax.profiler trace dir (XLA)
    trace_dir: Optional[str] = None      # distributed round spans land here
    #                                      (Perfetto trace_event JSON, one
    #                                      file per process; stitch with
    #                                      scripts/obs_report.py)
    telemetry: bool = False              # enable the counter/gauge/histogram
    #                                      registry; snapshot written to
    #                                      run_dir/telemetry.{json,prom}
    prom_port: int = 0                   # >0: serve live Prometheus text at
    #                                      :port/metrics (implies telemetry)
    metrics_port: int = 0                # alias for --prom_port (obs naming;
    #                                      setting BOTH to different ports is
    #                                      a config error, not a silent pick)
    perf: bool = False                   # performance flight recorder
    #                                      (obs/perf.py): one perf.jsonl
    #                                      ledger line per round/version —
    #                                      phase wall-times, wire bytes,
    #                                      peak host RSS, recompile sentry
    #                                      (cross_silo / async_fl server)
    perf_ledger: Optional[str] = None    # explicit ledger path (implies
    #                                      --perf; default run_dir/perf.jsonl)
    perf_strict: bool = False            # recompile sentry raises
    #                                      RecompileError instead of
    #                                      warning — the test/CI mode that
    #                                      makes a retracing hot function
    #                                      fail the run loudly (implies
    #                                      --perf)
    device_obs: bool = False             # device & compile observatory
    #                                      (obs/device.py): extend every
    #                                      perf.jsonl line with a device
    #                                      section — per-device memory
    #                                      watermarks (memory_stats, or
    #                                      the live-arrays CPU fallback),
    #                                      a named compile ledger (wall
    #                                      time per jit cache entry, and
    #                                      recompile warnings name the
    #                                      arg shape that changed), and
    #                                      an honest MFU gauge from XLA
    #                                      cost analysis (implies --perf;
    #                                      costs one extra cost-analysis
    #                                      compile per NEW jit cache
    #                                      entry, off the steady path)
    slo: str = ""                        # SLO threshold overrides for the
    #                                      serve deep health check, e.g.
    #                                      "round_duration_p95_seconds=10,
    #                                      serve_shed_rate=0.01" (names:
    #                                      obs/perf.DEFAULT_SLOS; includes
    #                                      the health_* drift-alarm
    #                                      thresholds of obs/health.py
    #                                      and the device-memory headroom
    #                                      objective of obs/device.py)
    health: bool = False                 # federation health observatory
    #                                      (obs/health.py): streaming
    #                                      per-round learning-health stats
    #                                      on the receive path — update-
    #                                      norm Welford moments, cosine
    #                                      alignment, per-silo fairness,
    #                                      drift alarms, one health.jsonl
    #                                      line per round/version
    #                                      (cross_silo / async_fl server)
    health_ledger: Optional[str] = None  # explicit health ledger path
    #                                      (implies --health; default
    #                                      run_dir/health.jsonl)
    log_stdout: bool = True
    # ---- chaos injection (comm/chaos.py over the local silo backend) ---
    # seeded per-message fault probabilities for --algo cross_silo
    # --silo_backend local; any non-zero value switches the local hub to
    # the threaded drive (delayed frames arrive on wall-clock timers)
    chaos_drop: float = 0.0              # drop prob (needs --straggler_policy
    #                                      drop + --round_timeout_s)
    chaos_delay: float = 0.0             # delay prob
    chaos_max_delay_s: float = 0.05      # delay bound (also reorder flush)
    chaos_dup: float = 0.0               # duplicate prob
    chaos_reorder: float = 0.0           # reorder (hold-back) prob
    chaos_corrupt: float = 0.0           # payload corruption prob (seeded
    #                                      bit-flip/NaN into model_params —
    #                                      the admission screen's sparring
    #                                      partner)
    chaos_seed: int = 0                  # fault-schedule seed

    # ---- crash consistency (utils/journal.py + robust/faultline.py) ----
    journal: bool = False            # durable round journal on the
    #                                  streaming-fold receive path: per-
    #                                  accept records appended crash-safe
    #                                  + periodic atomic fold-state
    #                                  snapshots, so a server killed
    #                                  MID-ROUND resumes the same round
    #                                  and re-tasks only silos whose
    #                                  uploads were not durably folded
    #                                  (bit-identical resume on the
    #                                  defended-mean stream path; secagg
    #                                  rounds are abort-only).  Requires
    #                                  --agg_mode stream (or --secagg);
    #                                  pair with --checkpoint_every 1 for
    #                                  mid-round recovery to engage
    journal_dir: Optional[str] = None  # explicit journal directory
    #                                  (implies --journal; default
    #                                  run_dir/journal; edges get
    #                                  journal/edge{e} subdirs)
    journal_snapshot_every: int = 4  # fold-state snapshot cadence in
    #                                  accepted folds (1 = every fold
    #                                  durable — tightest recovery window
    #                                  at one O(model) write per upload)

    # ---- checkpoint / resume (orbax round-level, SURVEY §5.4) ----------
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 10
    checkpoint_async: bool = False  # background orbax saves (training
    #                                 never blocks on I/O; durable at the
    #                                 next save/flush/close/read)
    checkpoint_keep_last_n: int = 0  # >0: retention GC — only the newest
    #                                  N round dirs survive (serve-while-
    #                                  train runs must not fill the disk
    #                                  the serving registry watches);
    #                                  0 = the checkpointer default (3)

    # ---- serving (fedml_tpu/serve: registry + batcher + HTTP frontend) -
    serve_port: int = 0             # >0 (cross_silo): serve the global
    #                                 model over HTTP while training —
    #                                 /predict /healthz /version /metrics
    serve_buckets: str = "1,2,4,8,16,32"  # micro-batch shape buckets
    #                                 (comma ints, strictly increasing;
    #                                 one jit compile per bucket)
    serve_deadline_ms: float = 50.0  # default per-request deadline; a
    #                                 request that waits this out in the
    #                                 queue is shed (429), not served late
    serve_queue_depth: int = 256    # admission control: submits beyond
    #                                 this many queued requests get 429
    serve_batch_delay_ms: float = 2.0  # micro-batch flush deadline: how
    #                                 long the oldest queued request may
    #                                 wait for batchmates
    serve_workers: int = 1          # >1: the multi-worker frontend
    #                                 (serve/pool.py) — N SO_REUSEPORT
    #                                 accept loops, each its own micro-
    #                                 batcher, over ONE shared registry;
    #                                 1 = the single ThreadingHTTPServer
    serve_best_effort_headroom: float = 0.5  # fraction of the queue
    #                                 depth best-effort requests may
    #                                 fill; past it (or while any SLO is
    #                                 breaching) best_effort sheds and
    #                                 interactive keeps the reserve

    # ---- release gate (fedml_tpu/serve/release: canary → promote) ------
    release_gate: bool = False      # gate every published global behind
    #                                 the canary release controller:
    #                                 shadow divergence + health alarms +
    #                                 held-out eval must all pass before
    #                                 the serving swap (requires
    #                                 --serve_port)
    release_shadow_every: int = 16  # shadow sampler: capture every Nth
    #                                 admitted /predict instance
    release_shadow_slots: int = 64  # shadow ring size (newest N kept)
    release_divergence_budget: float = 0.1  # max fraction of shadow rows
    #                                 where canary disagrees with live
    release_eval_tolerance: float = 0.02  # held-out eval may regress at
    #                                 most this much vs the last promoted
    release_cooldown_s: float = 5.0  # refuse new canaries this long
    #                                 after a rollback...
    release_backoff: float = 2.0    # ...growing exponentially per
    #                                 consecutive failure...
    release_max_cooldown_s: float = 60.0  # ...capped here
    wave_adversary: str = ""        # cross_device only: seeded poisoned
    #                                 wave summaries, injected pre-
    #                                 admission — "round:wave:kind[:param]"
    #                                 comma list (robust/adversary)


def build_parser() -> argparse.ArgumentParser:
    """Argparse surface generated from the dataclass — one flag per field,
    same names as the reference where a reference flag exists."""
    p = argparse.ArgumentParser(
        prog="python -m fedml_tpu",
        description="TPU-native federated learning experiments")
    for f in dataclasses.fields(ExperimentConfig):
        name = "--" + f.name
        default = f.default
        if f.type in ("Optional[str]", Optional[str]):
            p.add_argument(name, type=str, default=default)
        elif isinstance(default, bool):
            p.add_argument(name, type=lambda s: s.lower() in ("1", "true"),
                           default=default)
        elif isinstance(default, int):
            p.add_argument(name, type=int, default=default)
        elif isinstance(default, float):
            p.add_argument(name, type=float, default=default)
        else:
            p.add_argument(name, type=str, default=default)
    return p


def config_from_argv(argv=None) -> ExperimentConfig:
    args = build_parser().parse_args(argv)
    return ExperimentConfig(**vars(args))
