"""SCBF / SCBFwP orchestrator — the paper's Algorithm 1, faithfully.

One ``global loop``:
  1. the round scheduler picks the reporting cohort (full participation
     reproduces the paper; sampling / dropout / stragglers / buffered
     async are the cross-device scenarios of repro.fed.scheduler);
  2. the cohort engine trains every participant and channel-selects its
     delta (top-α channels by norm) — as one vmapped XLA program
     (repro.fed.engine.BatchedEngine) or the reference per-client loop;
  3. the aggregation strategy folds the uploads into the server:
     W <- W + Σ_k ΔW̃_k for SCBF (repro.fed.strategy);
  4. (SCBFwP) while the cumulative pruned fraction is below θ_total,
     prune θ of the server's hidden neurons by APoZ on the validation
     set and push the pruned structure to all clients;
  5. evaluate AUC-ROC / AUC-PR on the test set.

``run_federated`` is a thin driver over those three pluggable parts: it
owns PRNG-key derivation (so engine choice never changes the random
stream), the lr schedule (precomputed as a host-side table — no
per-loop device sync), differential privacy on the upload path
(optionally with subsampled-RDP amplification), and the per-loop
records with the communication accounting used by EXPERIMENTS.md
(§Paper-validation) and benchmarks/fig2.

With ``FedConfig.fuse_rounds = S > 1`` (sync mode, batched engine) the
driver switches to the **fused round loop** (``_run_fused``): S rounds
are pre-planned into one static device program — train → delta →
select → DP → on-device aggregation inside a single ``lax.scan`` — and
the trajectory stays bit-identical to the per-round path while
evaluation coarsens to chunk boundaries (docs/FED_ENGINE.md §Fused
round loop).  SCBFwP runs fused too when
``ScbfConfig.prune_impl = "mask"``: pruning becomes a static-shape
keep-mask (repro.core.pruning.Pruner) so geometry stays run-constant —
per-prune-epoch chunk splits, on-device APoZ at chunk boundaries, an
optional one-shot compaction when the budget is exhausted, and <= 2
fused compiles per run (docs/FED_ENGINE.md §Pruning on the fused
path).  Reshape-mode pruning keeps the per-round path.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ScbfConfig, TrainConfig
from repro.core import privacy, pruning
from repro.data.medical import MedicalCohort, dirichlet_split, federated_split
from repro.metrics.auc import auc_pr, auc_roc
from repro.models.mlp_net import init_mlp, mlp_forward
from repro.obs import checks as obschecks
from repro.obs import metrics as obsm
from repro.obs import trace as obstrace
from repro.optim import schedules


@dataclass
class LoopRecord:
    loop: int
    auc_roc: float               # last-known when evaluated=False
    auc_pr: float
    upload_fraction: float       # fraction of params revealed this loop
    sparse_bytes: int            # what SCBF actually ships
    dense_bytes: int             # what FedAvg would ship for the same model
    wall_time: float             # seconds for the loop (train+select+update)
    flops_proxy: float           # ~params * examples (pruning shrinks this)
    hidden_sizes: Tuple[int, ...] = ()
    num_participants: int = 0    # clients whose updates arrived this loop
    epsilon: Optional[float] = None   # cumulative DP ε (None: DP off)
    # False when this loop skipped evaluation (TrainConfig.eval_every,
    # or a fused loop that is not a chunk boundary): auc_roc/auc_pr then
    # carry the most recent evaluation so figures stay well-defined
    evaluated: bool = True
    # set only under ScbfConfig.dp_amplification: ``epsilon`` is then
    # the tighter of the subsampled-amplified and unamplified bounds
    # (both are valid) and this keeps the unamplified one for reference
    epsilon_unamplified: Optional[float] = None
    # mean per-participant train loss from the on-device telemetry
    # (repro.obs; None when collection was off this run)
    train_loss: Optional[float] = None
    # True on the fused path: ``wall_time`` is chunk wall / rounds — a
    # fair amortized figure, NOT a per-round measurement (the S rounds
    # ran as one device program, so no per-round wall exists)
    wall_is_amortized: bool = False
    # client slots the round's program trained: the fused chunk's
    # run-constant B, or the per-round engine's bucket (0 when nothing
    # was dispatched); ``slots - num_participants`` trained padding
    slots: int = 0


@dataclass
class RunResult:
    method: str
    records: List[LoopRecord] = field(default_factory=list)
    dp_delta: Optional[float] = None  # δ of the reported (ε, δ); None: DP off
    final_params: Optional[Tuple] = None  # the trained global model
    # flight-recorder watchdogs (repro.obs): compile-count deltas, span
    # and host-offload counters — populated only when the run executed
    # under an active ``obs.trace.recording`` (None otherwise)
    telemetry: Optional[dict] = None

    @property
    def final(self) -> LoopRecord:
        return self.records[-1]

    @property
    def final_epsilon(self) -> Optional[float]:
        """Cumulative (ε, δ)-DP ε spent over the whole run (None: DP off)."""
        return self.records[-1].epsilon if self.records else None

    def best(self, key: str = "auc_roc") -> float:
        return max(getattr(r, key) for r in self.records)

    def total_time(self) -> float:
        return sum(r.wall_time for r in self.records)

    def total_upload_bytes(self) -> int:
        return sum(r.sparse_bytes for r in self.records)


# module-level jit so every _evaluate call shares one compilation cache
# (a per-call jax.jit(...) wrapper recompiled on every evaluation)
_mlp_forward_jit = jax.jit(mlp_forward)


def _evaluate(params, x, y, batch: int = 8192, neuron_masks=None):
    with obstrace.span("eval", examples=int(x.shape[0])):
        scores = []
        for s in range(0, x.shape[0], batch):
            scores.append(np.asarray(_mlp_forward_jit(
                tuple(params), jnp.asarray(x[s:s + batch]), neuron_masks)))
        sc = jnp.asarray(np.concatenate(scores))
        yy = jnp.asarray(y)
        return float(auc_roc(sc, yy)), float(auc_pr(sc, yy))


def _compile_counts():
    """(scbf, fused) jit-cache sizes for the run_end watchdog delta."""
    from repro.fed.engine import fused_compile_count, scbf_compile_count
    return scbf_compile_count(), fused_compile_count()


def _finish_telemetry(result: RunResult, counts0) -> None:
    """Fold recorder counters + compile deltas into ``RunResult`` and
    emit the closing ``run_end`` event (no-op when not recording)."""
    rec = obstrace.get_recorder()
    if rec is None:
        return
    tel = dict(rec.counters)
    if counts0 is not None:
        counts1 = _compile_counts()
        tel["scbf_compiles"] = counts1[0] - counts0[0]
        tel["fused_compiles"] = counts1[1] - counts0[1]
    result.telemetry = tel
    rec.event("run_end", **tel)


def _partition(cohort: MedicalCohort, train_cfg: TrainConfig):
    fed = train_cfg.fed
    if fed.partition == "dirichlet":
        return dirichlet_split(cohort.x_train, cohort.y_train,
                               train_cfg.scbf.num_clients,
                               alpha=fed.dirichlet_alpha,
                               seed=train_cfg.seed)
    if fed.partition == "iid":
        return federated_split(cohort.x_train, cohort.y_train,
                               train_cfg.scbf.num_clients,
                               seed=train_cfg.seed)
    raise ValueError(f"unknown partition {fed.partition!r}; iid|dirichlet")


def _lr_schedule(train_cfg: TrainConfig):
    if train_cfg.lr_schedule == "cosine":
        return schedules.cosine_decay(train_cfg.learning_rate,
                                      max(train_cfg.global_loops - 1, 1))
    return schedules.constant(train_cfg.learning_rate)


def _lr_table(train_cfg: TrainConfig) -> np.ndarray:
    """Host-side lr table for the whole run, one device dispatch total.

    The loop used to ``float(lr_fn(...))`` every round — a device→host
    sync on the hot path.  Evaluating the schedule vmapped once kills
    that, and the same table slices into the fused path's (S,) lr
    array, so both paths read identical f32 values.
    """
    fn = _lr_schedule(train_cfg)
    steps = jnp.arange(max(train_cfg.global_loops, 1))
    return np.asarray(jax.vmap(fn)(steps), dtype=np.float32)


def _should_eval(loop: int, total_loops: int, eval_every: int) -> bool:
    """Evaluate every N loops, plus always the final loop."""
    return loop == total_loops - 1 or (loop + 1) % max(eval_every, 1) == 0


def _derive_round_keys(key, num_clients: int, part, P: int):
    """(next_key, ckeys, skeys, dp_keys) for one round — THE key-stream
    contract, shared by the per-round loop and the fused pre-planner so
    the two paths consume identical randomness by construction: one
    4-way split per round, training keys indexed by *client id* (so
    client k's key is independent of who else was sampled), and
    selection / DP keys split per participant.  Empty rounds still
    advance the stream (the 4-way split) but return empty key rows.
    """
    key, kc, ks, kd = jax.random.split(key, 4)
    if P:
        ckeys_all = jax.random.split(kc, num_clients)
        ckeys = ckeys_all[np.asarray(part)]
        skeys = jax.random.split(ks, P)
        dp_keys = jax.random.split(kd, P)
    else:
        empty = np.zeros((0, 2), np.uint32)
        ckeys = skeys = dp_keys = empty
    return key, ckeys, skeys, dp_keys


def run_federated(cohort: MedicalCohort,
                  train_cfg: TrainConfig,
                  method: str = "scbf",
                  mlp_features: Optional[Tuple[int, ...]] = None,
                  verbose: bool = False,
                  engine: Optional[str] = None) -> RunResult:
    """Run one federated experiment.

    method: "scbf" | "fedavg", with pruning controlled by
    ``train_cfg.scbf.prune`` (→ SCBFwP / FAwP).  ``engine`` overrides
    ``train_cfg.fed.engine`` ("batched" vmapped cohort | "sequential"
    reference loop); both consume the same PRNG stream, and for
    equal-size shards (the paper's IID split) they produce identical
    trajectories.  The batched engine buckets the per-round participant
    count (``fed.bucket``) so varying P under sampling/dropout does not
    recompile, and shards the bucketed cohort over a pod mesh when
    ``fed.pods > 1`` (docs/FED_ENGINE.md).  ``fed.fuse_rounds > 1``
    runs whole chunks of sync rounds as one device program with
    on-device aggregation (bit-identical trajectory; evaluation at
    chunk boundaries only), falling back to the per-round loop for
    reshape-mode pruning, fedbuff, or the sequential engine —
    mask-mode pruning (``scbf.prune_impl="mask"``) runs fused
    first-class.  Rounds where every
    sampled client drops out are skipped cleanly (no P=0 dispatch).
    Ragged cohorts (Dirichlet) batch differently —
    the padded engine runs ``n_max // B`` masked batches per epoch
    while the sequential loop runs ``n_k // B`` — so there the engine
    choice selects between two legitimate trainings, not two
    implementations of one (docs/FED_ENGINE.md §Caveats).
    """
    # deferred: repro.fed modules import repro.core.* at module scope, so
    # importing them here (not at module top) keeps repro.core importable
    # from either direction
    from repro.fed.clock import SimClock
    from repro.fed.engine import make_engine
    from repro.fed.faults import (FaultInjector, Resilience,
                                  apply_payload_faults)
    from repro.fed.scheduler import make_scheduler
    from repro.fed.strategy import (AdmissionPolicy, RoundContribution,
                                    admit_payloads, make_strategy)

    cfg: ScbfConfig = train_cfg.scbf
    fed = train_cfg.fed
    if method not in ("scbf", "fedavg"):
        raise ValueError(method)
    if cfg.dp_noise_multiplier > 0 and method != "scbf":
        raise ValueError("dp_noise_multiplier applies to the sparse scbf "
                         "upload path; method='fedavg' ships full weights "
                         "with no DP mechanism — refusing to run with a "
                         "privacy guarantee silently off")
    if cfg.prune and cfg.prune_impl not in ("reshape", "mask"):
        raise ValueError(f"unknown prune_impl {cfg.prune_impl!r}; "
                         "one of ('reshape', 'mask')")
    mask_prune = cfg.prune and cfg.prune_impl == "mask"
    if mask_prune and method != "scbf":
        raise ValueError("prune_impl='mask' threads neuron keep-masks "
                         "through the sparse scbf pipeline; "
                         "method='fedavg' (FAwP) prunes by reshaping — "
                         "use prune_impl='reshape'")
    if fed.mode == "fedbuff":
        if method != "scbf":
            raise ValueError("fedbuff buffers sparse scbf uploads; "
                             "method must be 'scbf'")
        if cfg.prune and not mask_prune:
            raise ValueError("reshape pruning changes shapes under "
                             "in-flight clients; fedbuff needs "
                             "prune_impl='mask' (run-constant geometry)")

    # ---- resilience configuration (repro.fed.clock / .faults) ----
    clock_on = fed.clock.enabled
    faults_on = fed.faults.enabled
    spill_mode = clock_on and fed.clock.deadline_action == "spill"
    if faults_on and method != "scbf":
        raise ValueError(
            "fault injection corrupts the sparse scbf upload pipeline; "
            "method='fedavg' ships full weight pytrees with no wire "
            "payload to corrupt — refusing a silently-inert fault plan")
    if fed.max_update_norm > 0 and method != "scbf":
        raise ValueError(
            "max_update_norm bounds sparse scbf payload norms; the "
            "fedavg path has no payload to gate — refusing to run with "
            "a configured bound silently off")
    if fed.min_valid_participants > 0 and fed.mode == "fedbuff":
        raise ValueError(
            "round-level quorum retries re-plan the round; fedbuff "
            "planning mutates in-flight client state on every plan "
            "call, so a replanned attempt would corrupt it — "
            "min_valid_participants needs sync mode")
    if spill_mode and cfg.prune:
        raise ValueError(
            "deadline spilling delivers payloads emitted against an "
            "earlier round's keep-masks; pruning changes the masks "
            "between emission and arrival, so the spilled indices "
            "would remap wrong — use deadline_action='drop' with "
            "pruning")

    # job set-up, from the model's initialisation to the round loop, is a
    # span of its own so that profiles name it; ``cohort_put`` inside it
    # is the engine build with the cohort's copy to the device
    with obstrace.span("job_setup"):
        feats = mlp_features or (cohort.num_features, 256, 64, 1)
        key = jax.random.PRNGKey(train_cfg.seed)
        key, init_key = jax.random.split(key)
        params = init_mlp(feats, init_key)

        clients = _partition(cohort, train_cfg)
        with obstrace.span("cohort_put"):
            eng = make_engine(engine or fed.engine, clients,
                              train_cfg.local_batch_size,
                              train_cfg.local_epochs,
                              bucket=fed.bucket, pods=fed.pods)
            # the engine holds the cohort now: free the host shards here,
            # inside set-up, not when the job returns (about 20 ms for the
            # paper's 215 MB cohort on a v5e host)
            del clients
        clock = SimClock(cfg.num_clients, fed.clock, seed=train_cfg.seed) \
            if clock_on else None
        scheduler = make_scheduler(fed, cfg.num_clients, train_cfg.seed,
                                   clock=clock)
        injector = FaultInjector(cfg.num_clients, fed.faults) \
            if faults_on else None
        # the admission gate arms whenever payloads can be hostile (fault
        # injection) or a norm bound is configured; otherwise the strategies
        # keep their zero-overhead fault-free hot path
        policy = AdmissionPolicy(max_update_norm=fed.max_update_norm,
                                 norm_action=fed.norm_action) \
            if (faults_on or fed.max_update_norm > 0) else None
        strategy = make_strategy(method, cfg, fed, policy=policy)
        resil = Resilience(scheduler, clock, injector, fed)
        if fed.min_valid_participants > 0 and \
                fed.min_valid_participants > scheduler.max_participants:
            raise ValueError(
                f"min_valid_participants={fed.min_valid_participants} can "
                f"never be met: the scheduler samples at most "
                f"{scheduler.max_participants} clients per round — every "
                "round would exhaust its retries and miss quorum")
        state = strategy.init(params)
        # fedbuff only: stale version snapshots (sync trains on the current
        # params, so keeping the initial model alive would be pure waste)
        history = {0: params} if fed.mode == "fedbuff" else None
        # spill mode: round-keyed snapshots — a spilled client trains from
        # the params of the round it was sampled in, delivered rounds later
        round_history = {0: params} if spill_mode else None
        # host-side lr table: one device dispatch for the whole run instead
        # of a float() sync per loop, and the fused path's (S,) lr array
        lrs = _lr_table(train_cfg)

        if cfg.dp_noise_multiplier < 0:
            raise ValueError(
                f"dp_noise_multiplier must be >= 0, got "
                f"{cfg.dp_noise_multiplier}: the DP gate is "
                f"'dp_noise_multiplier > 0', so a negative value would "
                f"silently run without DP while looking configured")
        dp_on = method == "scbf" and cfg.dp_noise_multiplier > 0
        if dp_on:
            # fail fast on an unknown accountant or a classic-bound run
            # outside its eps <= 1 domain, not after a full training loop
            privacy.epsilon_for(cfg.dp_noise_multiplier, cfg.dp_delta,
                                loops=1, accountant=cfg.dp_accountant)
        amplify = dp_on and cfg.dp_amplification
        amp_q = 1.0
        if amplify:
            if clock_on:
                raise ValueError(
                    "subsampled amplification assumes a uniform i.i.d. "
                    "per-round sample; the simulated clock restricts "
                    "sampling to currently-available clients (diurnal "
                    "churn), which is not one — refusing to report a "
                    "silently-wrong amplified ε")
            if fed.mode == "fedbuff":
                raise ValueError(
                    "subsampled amplification assumes an i.i.d. per-round "
                    "sample; fedbuff participation is not one — refusing to "
                    "report a silently-wrong amplified ε")
            if cfg.dp_accountant != "rdp":
                raise ValueError("dp_amplification is an RDP analysis; it "
                                 f"composes on the subsampled RDP curve, so "
                                 f"dp_accountant={cfg.dp_accountant!r} cannot "
                                 "back the reported ε — use 'rdp'")
            # q from the scheduler's own cohort-size formula, so the
            # reported amplification always matches the sampling performed
            amp_q = min(1.0, scheduler.max_participants / cfg.num_clients)
            privacy.amplified_epsilon_for(cfg.dp_noise_multiplier, amp_q,
                                          cfg.dp_delta, rounds=1)  # fail fast
        # ε composes per *release*, not per loop: under sampling, dropout or
        # fedbuff a client uploads in only some rounds, so the spend is
        # tracked per client and the worst (most-releasing) client reported.
        # (The amplified curve instead composes over rounds — every round is
        # one inclusion trial for every client.)
        dp_releases = np.zeros(cfg.num_clients, dtype=np.int64)
        pruner = None
        if cfg.prune:
            # fedbuff keeps full-geometry stale snapshots alive for its
            # in-flight clients, so the one-shot mask-mode compaction must
            # stay off there (mixed geometries could never stack)
            pruner = pruning.Pruner(
                params, cohort.x_val, prune_rate=cfg.prune_rate,
                prune_total=cfg.prune_total, impl=cfg.prune_impl,
                compact=cfg.prune_compact and fed.mode != "fedbuff")
        result = RunResult(method=method + ("wp" if cfg.prune else ""),
                           dp_delta=cfg.dp_delta if dp_on else None)

        # ---- flight recorder (repro.obs, docs/OBSERVABILITY.md) ----
        # device telemetry is the config's switch alone, so a recorded run
        # compiles the programs an unrecorded one does; the compile-count
        # watchdog only samples while recording (it touches jit caches,
        # and un-recorded runs shouldn't)
        collect = train_cfg.obs.device_metrics
        counts0 = _compile_counts() if obstrace.get_recorder() is not None \
            else None
        obstrace.event(
            "run_start", method=result.method, loops=train_cfg.global_loops,
            clients=cfg.num_clients, engine=eng.name,
            fuse_rounds=int(fed.fuse_rounds), mode=fed.mode,
            dp_sigma=(cfg.dp_noise_multiplier * cfg.dp_clip_norm)
            if dp_on else None,
            prune=cfg.prune, prune_impl=cfg.prune_impl if cfg.prune else None)

    def _epsilons(loop: int):
        """(epsilon, epsilon_unamplified) for the record of ``loop``."""
        if not dp_on:
            return None, None
        un = privacy.epsilon_for(cfg.dp_noise_multiplier, cfg.dp_delta,
                                 loops=int(dp_releases.max()),
                                 accountant=cfg.dp_accountant)
        if amplify:
            # both accountings are valid upper bounds — amplified
            # composes over rounds, unamplified over per-client
            # releases — so report the tighter of the two: under
            # dropout with q ≈ 1 the release ledger can actually win
            # (fewer releases than rounds, no amplification to offset)
            amp = privacy.amplified_epsilon_for(
                cfg.dp_noise_multiplier, amp_q, cfg.dp_delta,
                rounds=loop + 1)
            return min(amp, un), un
        return un, None

    init_params = params
    known = {"roc": None, "pr": None}

    def _metrics(params_now, do_eval: bool, nmasks=None):
        """(auc_roc, auc_pr, evaluated) — last-known when not evaluating.

        ``nmasks`` evaluates the masked model (mask-mode SCBFwP): the
        pruned-and-masked network is the model the run is training, so
        it is the one the records must score.  Before any evaluation
        has happened the last-known model is the initial one, scored
        lazily so the default config (eval_every=1, unfused) never pays
        for it.
        """
        if do_eval:
            known["roc"], known["pr"] = _evaluate(params_now,
                                                  cohort.x_test,
                                                  cohort.y_test,
                                                  neuron_masks=nmasks)
            return known["roc"], known["pr"], True
        if known["roc"] is None:
            known["roc"], known["pr"] = _evaluate(init_params,
                                                  cohort.x_test,
                                                  cohort.y_test)
        return known["roc"], known["pr"], False

    if int(fed.fuse_rounds) < 1:
        raise ValueError(f"fuse_rounds must be >= 1, got {fed.fuse_rounds}")
    # the fused path needs: sync planning (fedbuff wants per-round server
    # feedback), static shapes (reshape pruning changes them mid-run;
    # MASK pruning keeps geometry run-constant and fuses first-class),
    # and the batched engine (there is no sequential program to fuse) —
    # anything else falls back to the per-round loop below
    use_fused = (int(fed.fuse_rounds) > 1 and fed.mode == "sync"
                 and (not cfg.prune or mask_prune)
                 and eng.name == "batched" and not spill_mode)
    if use_fused:
        # the fused path aggregates on device from per-slot admit masks
        # decided at PLAN time (repro.fed.faults); a host-side admission
        # verdict that cannot be predicted at plan time would silently
        # diverge from what the device folded in — refuse those combos
        # up front rather than diverge
        if fed.max_update_norm > 0 and not faults_on:
            raise ValueError(
                "the fused path cannot run a host-side norm gate over "
                "its on-device aggregation; arm the fault model "
                "(FaultConfig.enabled) so admission is planned, or use "
                "fuse_rounds=1")
        if faults_on and fed.max_update_norm > 0 \
                and fed.norm_action == "clip":
            raise ValueError(
                "norm_action='clip' rescales admitted payloads on the "
                "host; the fused path aggregates the raw on-device "
                "deltas, so clipping cannot take effect — use "
                "norm_action='reject' or fuse_rounds=1")
        if faults_on and fed.faults.poison_rate > 0 \
                and not (fed.max_update_norm > 0
                         and fed.norm_action == "reject"):
            raise ValueError(
                "poisoned (norm-inflated) updates are only excludable "
                "at plan time when a reject-mode norm gate is armed "
                "(max_update_norm > 0, norm_action='reject'); without "
                "one the fused path would fold poison into the model — "
                "arm the gate or use fuse_rounds=1")
        _run_fused(cohort, train_cfg, method, eng, resil, state, key,
                   lrs, dp_releases, result, _epsilons, _metrics, verbose,
                   pruner, collect, injector=injector, policy=policy)
        _finish_telemetry(result, counts0)
        return result

    prev_eps = 0.0
    for loop in range(train_cfg.global_loops):
        # one span is the loop's single wall-clock source: the region it
        # covers (schedule → train → aggregate → prune) is exactly what
        # the old hand-rolled perf_counter pair measured — evaluation
        # stays outside, as before
        with obstrace.span("round", loop=loop) as sp:
            lr = float(lrs[loop])
            ar = resil.plan_round(loop, state.version)
            plan = ar.plan
            part = plan.participants
            P = plan.num_participants
            if method == "scbf":
                # aborted quorum attempts trained and uploaded before
                # the server discarded them — their privacy spend is
                # real and must never be under-reported.  Each aborted
                # attempt is a DISTINCT (simulated) upload, so two
                # increments on this path are two releases, not one
                # double-counted — charging them is conservative in
                # exactly the direction DP accounting must err.
                for aborted in ar.aborted_arrivers:
                    if aborted.size:
                        dp_releases[np.asarray(aborted)] += 1  # privlint: disable=PL004

            key, ckeys, skeys, dp_keys = _derive_round_keys(
                key, cfg.num_clients, part, P)

            payloads, stats, dm = [], [], None
            wire_payloads = []
            if P:
                if fed.mode == "fedbuff":
                    params_for = [history[state.version - int(tau)]
                                  for tau in plan.staleness]
                elif spill_mode:
                    # spilled arrivals trained from the round they were
                    # sampled in (staleness = rounds in flight)
                    params_for = [round_history[loop - int(tau)]
                                  for tau in plan.staleness]
                else:
                    params_for = state.params
                if method == "scbf":
                    nmasks = pruner.masks if pruner is not None else None
                    keep_eff = pruner.emission_keep if pruner is not None \
                        else None
                    out = eng.scbf_round(
                        params_for, part, lr, ckeys, skeys, dp_keys, cfg,
                        nmasks=nmasks, keep=keep_eff, collect=collect)
                    (payloads, stats, dm) = out if collect else \
                        (out[0], out[1], None)
                    dp_releases[np.asarray(part)] += 1
                    wire_payloads = payloads
                    nx = eng.counts[np.asarray(part)]
                    stal = np.asarray(plan.staleness)
                    cl = np.asarray(part)
                    if injector is not None and payloads:
                        # client faults → seal → wire faults → replays
                        wire_payloads, dup_src = apply_payload_faults(
                            payloads, cl, ar.corrupt, ar.duplicated,
                            loop, ar.attempts - 1, fed.faults,
                            fed.max_update_norm)
                        if dup_src:
                            nx = np.concatenate([nx, nx[dup_src]])
                            stal = np.concatenate([stal, stal[dup_src]])
                            cl = np.concatenate([cl, cl[dup_src]])
                    # mask mode ships effective-geometry payloads whose
                    # checksums seal the wire bytes; the strategy admits
                    # on those and expands the survivors to the server's
                    # full geometry just before application
                    expand = None
                    if keep_eff is not None:
                        expand = (lambda ps, _k=keep_eff,
                                  _ref=state.params:
                                  pruning.expand_payloads(ps, _k, _ref))
                    contrib = RoundContribution(
                        num_examples=nx, staleness=stal,
                        payloads=wire_payloads, clients=cl,
                        expand=expand)
                else:
                    out = eng.fedavg_round(params_for, part, lr, ckeys,
                                           collect=collect)
                    (client_params, counts, dm) = out if collect else \
                        (out[0], out[1], None)
                    contrib = RoundContribution(
                        num_examples=counts, staleness=plan.staleness,
                        client_params=client_params,
                        clients=np.asarray(part))
                if ar.quorum_ok:
                    state = strategy.aggregate(state, contrib)
                # terminal quorum miss: the cohort trained and uploaded,
                # but the server refuses to step on a sub-quorum round
                # (the planner already emitted the quorum_miss event)
            params = state.params
            if fed.mode == "fedbuff":
                history[state.version] = params
                live = scheduler.referenced_versions() | {state.version}
                history = {v: p for v, p in history.items() if v in live}
            elif spill_mode:
                round_history[loop + 1] = params
                live = scheduler.referenced_rounds() | {loop + 1}
                round_history = {r: p for r, p in round_history.items()
                                 if r in live}

            # ---- communication accounting ----
            if method == "scbf":
                up_frac = float(np.mean([s.upload_fraction
                                         for s in stats])) if stats else 0.0
                # measured bytes of the encoded payloads (single source
                # of truth: repro.comm.wire), not a mask-count model —
                # wire_payloads includes replayed duplicates: bytes that
                # really crossed the network
                sparse_bytes = int(np.sum([p.nbytes
                                           for p in wire_payloads])) \
                    if wire_payloads else 0
                dense_bytes = int(np.sum([p.dense_nbytes
                                          for p in payloads])) \
                    if payloads else 0
            else:
                total = sum(int(np.prod(l["w"].shape))
                            + int(l["b"].shape[0]) for l in params)
                up_frac = 1.0 if P else 0.0
                dense_bytes = total * 4 * P
                sparse_bytes = dense_bytes

            # ---- pruning (SCBFwP / FAwP) ----
            if pruner is not None and pruner.active:
                # reshape: returns the compacted pytree; mask: updates
                # the keep-masks in place and returns params unchanged
                params = pruner.step(params)
                state = dataclasses.replace(state, params=params)
                obstrace.event("prune", loop=loop,
                               hidden=list(pruner.hidden_sizes()))
            if pruner is not None and pruner.should_compact:
                # mask mode, budget exhausted: one-shot compaction
                params = pruner.compact(params)
                state = dataclasses.replace(state, params=params)
                obstrace.event("compact", loop=loop,
                               hidden=list(pruner.hidden_sizes()))

        wall = sp.elapsed
        roc, pr, evaluated = _metrics(
            params, _should_eval(loop, train_cfg.global_loops,
                                 train_cfg.eval_every),
            pruner.masks if pruner is not None else None)
        eps, eps_un = _epsilons(loop)
        if pruner is not None:
            # effective model: identical whether neurons are masked,
            # compacted, or (reshape mode) physically gone
            n_params = pruner.effective_param_count(params)
            hidden = pruner.hidden_sizes()
        else:
            n_params = sum(int(np.prod(l["w"].shape)) + int(l["b"].shape[0])
                           for l in params)
            hidden = tuple(pruning.hidden_sizes(params))
        rec = LoopRecord(
            loop=loop, auc_roc=roc, auc_pr=pr,
            upload_fraction=up_frac,
            sparse_bytes=sparse_bytes, dense_bytes=dense_bytes,
            wall_time=wall,
            flops_proxy=float(n_params) * cohort.x_train.shape[0],
            hidden_sizes=hidden,
            num_participants=P, slots=eng.round_slots(P),
            epsilon=eps, evaluated=evaluated, epsilon_unamplified=eps_un,
            train_loss=dm.get("train_loss") if dm else None)
        result.records.append(rec)
        if train_cfg.debug_checks:
            # host-side chunk-boundary assertions on already-offloaded
            # values; the traced program is identical either way
            obschecks.verify_round(params, dm, where=f"loop {loop}")
        obstrace.event("round", **_round_event_fields(
            rec, plan, pruner, dm, eps_step=(eps - prev_eps)
            if eps is not None else None))
        prev_eps = eps if eps is not None else 0.0
        if verbose:
            print(f"[{result.method}] loop {loop:02d} "
                  f"auc_roc={roc:.4f} auc_pr={pr:.4f} "
                  f"upload={up_frac:.2%} hidden={rec.hidden_sizes} "
                  f"clients={P} t={wall:.2f}s")
    result.final_params = params
    _finish_telemetry(result, counts0)
    return result


def _round_event_fields(rec: LoopRecord, plan, pruner, dm,
                        eps_step=None) -> dict:
    """The ``round`` event's field dict (docs/OBSERVABILITY.md schema).

    One builder for both loop shapes so the per-round and fused paths
    emit identical event structure: LoopRecord scalars + scheduler
    telemetry (sampled/dropped/stragglers/staleness) + keep-mask density
    + the on-device metrics dict when collection was on.
    """
    out = {
        "loop": rec.loop, "participants": rec.num_participants,
        "slots": rec.slots,
        "upload_fraction": round(rec.upload_fraction, 6),
        "sparse_bytes": rec.sparse_bytes, "dense_bytes": rec.dense_bytes,
        "wall": round(rec.wall_time, 6),
        "wall_is_amortized": rec.wall_is_amortized,
        "hidden": list(rec.hidden_sizes),
        "evaluated": rec.evaluated,
    }
    if rec.epsilon is not None:
        out["epsilon"] = rec.epsilon
        if eps_step is not None:
            out["epsilon_step"] = eps_step
    if pruner is not None:
        out["keep_density"] = round(
            sum(pruner.hidden_sizes()) / max(pruner.original_hidden, 1),
            6)
    if plan is not None and hasattr(plan, "telemetry"):
        out.update(plan.telemetry())
    if dm:
        for k in ("train_loss", "selected", "codec_bytes"):
            if dm.get(k) is not None:
                out[k] = dm[k]
    return out


class _Chunk(NamedTuple):
    """One planned fused chunk: what the driver dispatches and what its
    records read back."""
    prune_active: bool
    ars: list           # the AdmittedRound of each real round
    fplan: object       # the engine's FusedPlan
    keep: Optional[List[np.ndarray]]  # emission keep sets (mask mode)
    version: int        # the server version after the chunk


def _run_fused(cohort: MedicalCohort, train_cfg: TrainConfig, method: str,
               eng, resil, state, key, lrs: np.ndarray,
               dp_releases: np.ndarray, result: RunResult,
               _epsilons, _metrics, verbose: bool, pruner=None,
               collect: bool = False, injector=None, policy=None) -> None:
    """The fused round loop: S sync rounds per device program.

    Each chunk is pre-planned into static (S, B) participant/validity
    arrays (``scheduler.plan_horizon`` + ``eng.prepare_fused_plan``),
    its PRNG keys pre-split from the *same stream* the per-round loop
    would consume, and its lr values sliced from the precomputed table —
    then train → delta → select → DP → on-device aggregation runs as
    one ``lax.scan`` with zero host crossings (fed/engine
    ``_fused_scbf_rounds``).  Wire encoding happens once per chunk from
    the returned (S, B) masked deltas, so per-round upload accounting is
    byte-identical to the per-round path.  Evaluation coarsens to chunk
    boundaries (docs/FED_ENGINE.md §Fused round loop).

    The chunks run as a two-stage pipeline: the next chunk is planned
    while this one runs, and dispatched as soon as this one is done and
    its model has been read (the boundary evaluation and debug checks:
    the dispatch donates that model's buffers), so the device trains
    chunk k+1 while the host pulls, encodes and records chunk k.  Plans
    and keys are drawn in the same order as without the pipeline.  The
    driver runs ahead only where chunk k+1's inputs do not depend on
    chunk k's outputs: never from the last chunk, nor while pruning
    (the prune step after a chunk sets the next chunk's keep-masks).

    SCBFwP (``pruner``, always mask-mode here): geometry stays
    run-constant, the keep-mask tuple rides into each chunk as a plain
    input, and chunks shrink to single rounds while pruning is still
    removing neurons (``fused_chunk_len``) so the APoZ → mask update at
    each chunk boundary lands at exactly the per-round cadence — the
    keep-mask trajectory is the per-round loop's by construction.
    Prune-phase chunks plan at horizon 1 (a degenerate one-round scan,
    still on-device aggregation and zero host crossings) rather than
    padding to S — one extra compiled program instead of S-1 garbage
    rounds per prune epoch — and the post-pruning phase pads to the
    run-constant (S, B) horizon as usual, so a whole SCBFwP run costs
    at most two fused compiles: the horizon-1 masked program and the
    horizon-S program (post-compaction geometry when ``prune_compact``,
    masked full geometry otherwise).
    """
    from repro.fed.cohort import fused_chunk_len
    from repro.fed.faults import apply_payload_faults
    from repro.fed.strategy import RoundContribution, admit_payloads

    cfg: ScbfConfig = train_cfg.scbf
    fed = train_cfg.fed
    scheduler = resil.scheduler
    S = int(fed.fuse_rounds)
    B = eng.fused_num_slots(scheduler.max_participants)
    total_loops = train_cfg.global_loops

    def _model_stats():
        """(n_params, hidden_sizes) of the current effective model."""
        if pruner is not None:
            return (pruner.effective_param_count(state.params),
                    pruner.hidden_sizes())
        n = sum(int(np.prod(l["w"].shape)) + int(l["b"].shape[0])
                for l in state.params)
        return n, tuple(pruning.hidden_sizes(state.params))

    def _prune_active() -> bool:
        return pruner is not None and pruner.active

    def _plan(loop0: int) -> _Chunk:
        """Plan the chunk that starts at ``loop0`` from the server state
        after the chunk before it (its version, its model's shapes)."""
        nonlocal key
        prune_active = _prune_active()
        chunk = fused_chunk_len(total_loops - loop0, S, prune_active)
        with obstrace.span("plan", rounds=chunk):
            # the resilient planner replaces plan_horizon: same
            # scheduler.plan sequence underneath (bit-parity when the
            # fault model is off), plus fault outcomes and quorum
            # resolved per round at plan time — which is what lets the
            # admission verdicts fold into the static (S, B) admit mask
            ars = [resil.plan_round(loop0 + i, state.version)
                   for i in range(chunk)]
            parts, cks, sks, dks, wts = [], [], [], [], []
            for ar in ars:
                part = ar.plan.participants
                P = ar.plan.num_participants
                # _derive_round_keys is the single key-stream contract,
                # so the fused pre-planner consumes EXACTLY what the
                # per-round loop would have
                key, ck, sk, dk = _derive_round_keys(key, cfg.num_clients,
                                                     part, P)
                cks.append(np.asarray(ck))
                sks.append(np.asarray(sk))
                dks.append(np.asarray(dk))
                parts.append(part)
                if method == "fedavg":
                    if P and ar.quorum_ok:
                        n = eng.counts[np.asarray(part)].astype(np.float64)
                        wts.append((n / n.sum()).astype(np.float32))
                    else:
                        # quorum-missed rounds must not step: all-zero
                        # weights pass the fedavg carry through bitwise
                        wts.append(np.zeros(P, np.float32))
            keep_eff = pruner.emission_keep if pruner is not None else None
            eff = obsm.effective_leaf_sizes(state.params, keep_eff) \
                if (collect and method == "scbf"
                    and keep_eff is not None) else None
            admits = [ar.admit_mask() for ar in ars] if resil.active \
                else None
            fplan = eng.prepare_fused_plan(
                parts, lrs[loop0:loop0 + chunk], cks, sks, dks,
                horizon=1 if prune_active else S, num_slots=B,
                weights=wts if method == "fedavg" else None,
                eff_sizes=eff, admit=admits)
        # a round bumps the version iff it passed quorum AND at least
        # one slot was admitted — the same rule ScbfSum's admission gate
        # applies on the per-round path (fault-free, admit == valid,
        # this is the old "any participants" count)
        applied = sum(1 for ar in ars
                      if ar.quorum_ok and bool(ar.admit_mask().any()))
        return _Chunk(prune_active, ars, fplan, keep_eff,
                      state.version + applied)

    def _dispatch(c: _Chunk):
        """Start chunk ``c`` on the device from the current model (whose
        buffers the call donates on backends that support donation).

        Returns ``(new_params, (masked_s, masks_s), met_s)``: the
        stacks to emit are None for FedAvg, the on-device metrics None
        unless ``collect``.  One return, after the branches: privlint's
        taint analysis joins a function's returns, and a join would let
        FedAvg's un-noised model stand for SCBF's in the next chunk.
        """
        if method == "fedavg":
            res = eng.fused_fedavg_chunk(state.params, c.fplan,
                                         collect=collect)
            out = (res[0], None, res[1]) if collect else (res, None, None)
        else:
            res = eng.fused_scbf_chunk(
                state.params, c.fplan, cfg,
                nmasks=pruner.masks if pruner is not None else None,
                collect=collect)
            out = res[0], (res[1], res[2]), res[3] if collect else None
        return out

    if min(S, total_loops) > 1:
        # the first chunk's non-boundary records will need last-known
        # metrics, so the initial-model evaluation always happens — do
        # it NOW, before the chunk call donates the initial params'
        # buffers on backends that support donation (a lazy evaluation
        # afterwards would read deleted arrays)
        _metrics(state.params, True,
                 pruner.masks if pruner is not None else None)

    # the key stream is split on the host's CPU backend (threefry gives
    # the same bits on every backend), so planning chunk k+1 does not
    # queue behind chunk k's program: a device runs its programs in order
    key = jax.device_put(key, jax.devices("cpu")[0])
    loop0 = 0
    prev_eps = 0.0
    cur = out = None       # the chunk dispatched ahead, and its outputs
    # chunks dispatched before their predecessor's emission (present,
    # at 0, on every recorded fused run)
    obstrace.count("fused_dispatch_ahead", 0)
    while loop0 < total_loops:
        # a chunk planned ahead was planned with this same pruner state
        chunk = fused_chunk_len(total_loops - loop0, S, _prune_active())
        nxt = nxt_out = None
        # the chunk span (its wall / rounds is each record's wall time)
        # covers (when nothing ran ahead) this chunk's plan and dispatch
        # → the next chunk's plan → the wait → pruning and the boundary
        # evaluation → the next chunk's dispatch → this chunk's emission
        with obstrace.span("fused_chunk", loop0=loop0, rounds=chunk) as sp:
            if cur is None:
                cur = _plan(loop0)
                out = _dispatch(cur)
            new_params, stacks, met_s = out
            state = dataclasses.replace(state, params=new_params,
                                        version=cur.version)
            ahead = loop0 + chunk < total_loops and not cur.prune_active
            if ahead:
                # host planning under this chunk's program
                nxt = _plan(loop0 + chunk)
            with obstrace.span("emit_wait"):
                jax.block_until_ready(out)
            # everything that reads this chunk's model runs before the
            # next dispatch donates it: telemetry, checks, pruning, the
            # boundary evaluation
            round_metrics = obsm.offload(met_s, rounds=cur.fplan.rounds) \
                if collect else None
            if train_cfg.debug_checks:
                # host-side, on the values the chunk already offloaded
                obschecks.verify_round(state.params, round_metrics,
                                       where=f"chunk@loop {loop0}")
            if cur.prune_active:
                # chunk boundary == per-round cadence while pruning
                # (chunks are 1 round long): APoZ on device, mask update
                # on host
                pruner.step(state.params)
                obstrace.event("prune", loop=loop0,
                               hidden=list(pruner.hidden_sizes()))
                if pruner.should_compact:
                    state = dataclasses.replace(
                        state, params=pruner.compact(state.params))
                    obstrace.event("compact", loop=loop0,
                                   hidden=list(pruner.hidden_sizes()))
            n_params, hidden = _model_stats()
            quality = [_metrics(state.params,
                                r == chunk - 1 and _should_eval(
                                    loop0 + r, total_loops,
                                    train_cfg.eval_every),
                                pruner.masks if pruner is not None
                                else None)
                       for r in range(chunk)]
            if ahead:
                nxt_out = _dispatch(nxt)
                obstrace.count("fused_dispatch_ahead")
            if method == "scbf":
                masked_s, masks_s = stacks
                emitted = eng.emit_fused_payloads(masked_s, masks_s,
                                                  cur.fplan, keep=cur.keep)
            else:
                emitted = [([], [])] * chunk
        wall_each = sp.elapsed / chunk

        # the chunk's per-round records, while the next chunk runs
        with obstrace.span("records", loop0=loop0, rounds=chunk):
            for r, ar in enumerate(cur.ars):
                plan = ar.plan
                loop = loop0 + r
                P = plan.num_participants
                payloads, stats = emitted[r]
                dm = round_metrics[r] if round_metrics is not None else None
                if method == "scbf":
                    # aborted quorum attempts are distinct uploads (fresh
                    # keys each attempt): two increments = two releases
                    for aborted in ar.aborted_arrivers:
                        if aborted.size:
                            dp_releases[np.asarray(aborted)] += 1  # privlint: disable=PL004
                    wire_payloads = payloads
                    if injector is not None and payloads:
                        # re-run the fault pipeline + the REAL admission
                        # gate on the emitted wire artifacts: events/counts
                        # match the per-round path, and the verdicts are
                        # checked against the plan the device already
                        # folded in (any divergence is a hard error, never
                        # a silent one); the gate reads the server's
                        # nonces, never its (donated) model
                        cl = np.asarray(plan.participants)
                        wire_payloads, dup_src = apply_payload_faults(
                            payloads, cl, ar.corrupt, ar.duplicated, loop,
                            ar.attempts - 1, fed.faults, fed.max_update_norm)
                        if ar.quorum_ok:
                            if dup_src:
                                cl = np.concatenate([cl, cl[dup_src]])
                            gate_contrib = RoundContribution(
                                num_examples=np.zeros(len(wire_payloads),
                                                      np.int64),
                                staleness=np.zeros(len(wire_payloads),
                                                   np.int64),
                                payloads=wire_payloads, clients=cl)
                            _, kept_idx = admit_payloads(state, gate_contrib,
                                                         policy)
                            planned = {i for i in range(P)
                                       if not ar.will_reject[i]}
                            if set(kept_idx) != planned:
                                raise RuntimeError(
                                    f"fused admission mismatch at loop "
                                    f"{loop}: the device folded slots "
                                    f"{sorted(planned)} but the admission "
                                    f"gate admitted {sorted(kept_idx)} — "
                                    "an update failed a gate the planner "
                                    "could not predict (e.g. a natural "
                                    "nonfinite or norm violation); rerun "
                                    "with fuse_rounds=1")
                    up_frac = float(np.mean(
                        [s.upload_fraction for s in stats])) \
                        if stats else 0.0
                    sparse_bytes = int(np.sum([p.nbytes
                                               for p in wire_payloads])) \
                        if wire_payloads else 0
                    dense_bytes = int(np.sum([p.dense_nbytes
                                              for p in payloads])) \
                        if payloads else 0
                    if P:
                        dp_releases[np.asarray(plan.participants)] += 1
                else:
                    up_frac = 1.0 if P else 0.0
                    dense_bytes = n_params * 4 * P
                    sparse_bytes = dense_bytes
                roc, pr, evaluated = quality[r]
                eps, eps_un = _epsilons(loop)
                rec = LoopRecord(
                    loop=loop, auc_roc=roc, auc_pr=pr,
                    upload_fraction=up_frac,
                    sparse_bytes=sparse_bytes, dense_bytes=dense_bytes,
                    wall_time=wall_each,
                    flops_proxy=float(n_params) * cohort.x_train.shape[0],
                    hidden_sizes=hidden, num_participants=P, slots=B,
                    epsilon=eps, evaluated=evaluated,
                    epsilon_unamplified=eps_un,
                    train_loss=(dm or {}).get("train_loss")
                    if (dm and P) else None,
                    wall_is_amortized=True)
                result.records.append(rec)
                obstrace.event("round", **_round_event_fields(
                    rec, plan, pruner, dm if P else None,
                    eps_step=(eps - prev_eps) if eps is not None else None))
                prev_eps = eps if eps is not None else 0.0
                if verbose:
                    print(f"[{result.method}] loop {loop:02d} "
                          f"auc_roc={roc:.4f} auc_pr={pr:.4f} "
                          f"upload={up_frac:.2%} hidden={rec.hidden_sizes} "
                          f"clients={P} t={wall_each:.2f}s"
                          + ("" if evaluated else " (metrics carried)"))
        loop0 += chunk
        cur, out = nxt, nxt_out
    result.final_params = state.params
