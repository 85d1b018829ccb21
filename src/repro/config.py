"""Config system for the SCBF reproduction framework.

Everything is a frozen dataclass so configs are hashable, comparable and
usable as jit static arguments.  Architectures register themselves into
``repro.configs.ARCHS`` (see ``repro/configs/__init__.py``); input shapes
and meshes are defined here because they are shared across architectures.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Mapping, Optional, Sequence, Tuple


# ---------------------------------------------------------------------------
# Architecture
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ArchConfig:
    """A single architecture, as assigned from the public pool.

    ``family`` is one of dense | moe | ssm | hybrid | audio | vlm | mlp.
    Fields default to "off" so dense configs stay short.
    """

    name: str
    family: str
    source: str                      # citation (arXiv / model card)

    num_layers: int = 0
    d_model: int = 0
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    vocab_size: int = 0

    # --- attention flavour ---
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0       # chatglm3: 0.5 (2d RoPE on half the dims)
    sliding_window: int = 0          # 0 = full attention
    attention_every: int = 1         # jamba: 8 -> 1 attention layer per 8
    cross_attn_every: int = 0        # llama-3.2-vision: 5

    # --- MLA (DeepSeek-V2) ---
    use_mla: bool = False
    kv_lora_rank: int = 0
    qk_rope_dim: int = 64

    # --- MoE ---
    num_experts: int = 0
    num_shared_experts: int = 0
    experts_per_token: int = 0
    moe_every: int = 1               # apply MoE every k-th layer
    first_dense_layers: int = 0      # deepseek: first layer is dense
    moe_capacity_factor: float = 1.25

    # --- SSM (Mamba2 / SSD) ---
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256
    ssm_conv_width: int = 4

    # --- encoder / decoder ---
    encoder_layers: int = 0          # whisper: 24
    encoder_seq: int = 1500          # whisper frame count after conv stub

    # --- modality frontend stubs ---
    frontend: str = "none"           # none | audio | vision
    num_patch_tokens: int = 1024     # vision stub patch count

    # --- plain-MLP family (the paper's own model) ---
    mlp_features: Tuple[int, ...] = ()   # e.g. (2917, 256, 64, 1)

    # --- misc ---
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    activation: str = "silu"         # silu | gelu
    tie_embeddings: bool = False
    dtype: str = "bfloat16"

    # ------------------------------------------------------------------
    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def num_ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim if self.ssm_state else 0

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def supports_long_decode_natively(self) -> bool:
        """Sub-quadratic decode without the sliding-window variant."""
        return self.family in ("ssm", "hybrid")

    def param_count(self) -> int:
        """Analytic parameter count (total, incl. all experts)."""
        if self.family == "mlp":
            n = 0
            for fin, fout in zip(self.mlp_features[:-1], self.mlp_features[1:]):
                n += fin * fout + fout
            return n
        d, L = self.d_model, self.num_layers
        n = self.vocab_size * d                      # embed
        if not self.tie_embeddings:
            n += self.vocab_size * d                 # unembed
        for layer in range(L):
            n += self._layer_params(layer)
        if self.encoder_layers:
            for layer in range(self.encoder_layers):
                n += self._enc_layer_params()
        n += d                                        # final norm
        return n

    def _attn_params(self) -> int:
        d, H, KV, hd = self.d_model, self.num_heads, self.num_kv_heads, self.head_dim
        if self.use_mla:
            r, rd = self.kv_lora_rank, self.qk_rope_dim
            n = d * H * (hd + rd)                    # q proj (nope+rope)
            n += d * (r + rd)                        # kv down (+ shared k_rope)
            n += r * H * (hd + hd)                   # kv up (k_nope + v)
            n += H * hd * d                          # out
            return n
        n = d * H * hd + 2 * d * KV * hd + H * hd * d
        if self.qkv_bias:
            n += H * hd + 2 * KV * hd
        return n

    def _mlp_params(self, d_ff: int) -> int:
        return 3 * self.d_model * d_ff               # gated (wi, wg, wo)

    def _is_moe_layer(self, layer: int) -> bool:
        if not self.num_experts:
            return False
        if layer < self.first_dense_layers:
            return False
        return (layer % self.moe_every) == (self.moe_every - 1) \
            if self.moe_every > 1 else True

    def _is_attn_layer(self, layer: int) -> bool:
        if self.family == "ssm":
            return False
        if self.attention_every > 1:
            return (layer % self.attention_every) == (self.attention_every - 1)
        return True

    def _layer_params(self, layer: int) -> int:
        d = self.d_model
        n = 2 * d                                    # two norms
        if self._is_attn_layer(layer):
            n += self._attn_params()
        elif self.family in ("ssm", "hybrid"):
            di, s = self.d_inner, self.ssm_state
            nh = di // self.ssm_head_dim
            n += d * (2 * di + 2 * s + nh)           # in_proj (x,z,B,C,dt)
            n += self.ssm_conv_width * (di + 2 * s)  # conv
            n += nh * 2                              # A_log, D
            n += di * d                              # out_proj
        if self.cross_attn_every and (layer % self.cross_attn_every
                                      == self.cross_attn_every - 1):
            n += self._attn_params() + d
        if self._is_moe_layer(layer):
            n += self.num_experts * self._mlp_params(self.d_ff)
            n += self.num_shared_experts * self._mlp_params(self.d_ff)
            n += d * self.num_experts                # router
        else:
            n += self._mlp_params(self.d_ff)
        return n

    def _enc_layer_params(self) -> int:
        return 2 * self.d_model + self._attn_params() + self._mlp_params(self.d_ff)

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top-k experts only)."""
        if not self.num_experts:
            return self.param_count()
        d, L = self.d_model, self.num_layers
        n = self.param_count()
        for layer in range(L):
            if self._is_moe_layer(layer):
                inactive = self.num_experts - self.experts_per_token
                n -= inactive * self._mlp_params(self.d_ff)
        return n


# ---------------------------------------------------------------------------
# Input shapes
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                        # train | prefill | decode


INPUT_SHAPES: Mapping[str, ShapeConfig] = {
    "train_4k":    ShapeConfig("train_4k",    4_096,   256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768,  32,  "prefill"),
    "decode_32k":  ShapeConfig("decode_32k",  32_768,  128, "decode"),
    "long_500k":   ShapeConfig("long_500k",   524_288, 1,   "decode"),
}


# ---------------------------------------------------------------------------
# SCBF / training config
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ScbfConfig:
    """The paper's hyper-parameters (§2.1, Algorithm 1)."""

    upload_rate: float = 0.10        # alpha — fraction of channels uploaded
    selection: str = "positive"      # positive | negative (paper §2.1)
    num_clients: int = 5             # paper §2.2
    # pruning (SCBFwP)
    prune: bool = False
    prune_rate: float = 0.10         # theta — fraction pruned per loop
    prune_total: float = 0.47        # theta_total
    # how a pruned neuron is removed (repro.core.pruning):
    #   reshape  host-side slicing between loops — physically smaller
    #            models immediately, but every step recompiles every
    #            jitted program and the fused round loop cannot run
    #   mask     static-shape keep-masks — geometry stays run-constant
    #            (no recompiles, fused-path compatible, scbf only);
    #            with prune_compact the model is sliced down ONCE when
    #            the cumulative budget is exhausted
    prune_impl: str = "reshape"      # reshape | mask
    # mask mode: compact physically (one extra compile) the moment
    # pruning completes, so flops/bytes shrink for the rest of the run
    prune_compact: bool = True
    # scale-out knobs (beyond paper)
    factored: bool = True            # factored channel scores for big models
    compressed_exchange: bool = False  # top-k gather exchange across pods
    score_norm: bool = False         # per-layer score normalisation
    # differential privacy on the upload path (paper §4 future work):
    # Gaussian mechanism on the masked delta before wire encoding.
    dp_noise_multiplier: float = 0.0  # 0 = off; sigma = nm * dp_clip_norm
    dp_clip_norm: float = 1.0        # L2 clip bound S on the masked delta
    dp_delta: float = 1e-5           # delta of the reported (eps, delta)
    dp_accountant: str = "rdp"       # rdp (Gaussian RDP curve) | classic
    # subsampled-Gaussian privacy amplification (sync sampling only):
    # compose the Mironov et al. 2019 subsampled-RDP curve over rounds
    # with q = per-round inclusion probability.  Refused under fedbuff
    # (participation there is not an i.i.d. per-round sample) and under
    # the classic accountant (amplification is an RDP analysis).
    dp_amplification: bool = False


@dataclass(frozen=True)
class ClockConfig:
    """Simulated wall-clock model (repro.fed.clock.SimClock).

    Per-client compute/network latency distributions plus a diurnal
    availability trace, all a pure function of (seed, round, attempt):
    client k's median compute time is ``compute_med_s`` scaled by a
    lognormal per-client speed trait (``hetero_sigma``), with per-round
    lognormal jitter (``compute_sigma``); network time composes the
    same way.  When enabled, the sync scheduler replaces its coin-flip
    straggler model with deadline-based cohort cuts: the round deadline
    is the ``deadline_quantile`` of the cohort's latencies and misses
    either drop or spill into the FedBuff buffer with clock-derived
    staleness (``deadline_action``).
    """

    enabled: bool = False
    compute_med_s: float = 10.0      # median local-training seconds
    compute_sigma: float = 0.25      # per-round lognormal jitter (compute)
    hetero_sigma: float = 0.6        # per-client speed spread (lognormal)
    net_med_s: float = 2.0           # median upload/network seconds
    net_sigma: float = 0.5           # per-round lognormal jitter (network)
    deadline_quantile: float = 0.9   # server waits for this cohort quantile
    deadline_action: str = "drop"    # drop | spill (into the FedBuff buffer)
    # diurnal churn: availability oscillates over the simulated day with
    # a per-client phase (timezone); amplitude 0 = always-on clients
    availability_mean: float = 1.0
    diurnal_amplitude: float = 0.0
    day_s: float = 86400.0
    round_gap_s: float = 0.0         # fixed server overhead between rounds


@dataclass(frozen=True)
class FaultConfig:
    """Seeded fault injection (repro.fed.faults.FaultInjector).

    Every rate is per sampled participant per round; outcomes are a
    pure function of (seed, round, attempt, client) so any fault trace
    replays deterministically from its seed.  ``bitflip``/``nan``/
    ``poison`` are mutually exclusive per client (their rates must sum
    to <= 1).  Transient network failures retry with exponential
    backoff (``net_backoff_s * 2^i``) up to ``net_retries`` times
    before the upload is lost.
    """

    enabled: bool = False
    seed: int = 0                    # fault-trace seed (independent of run)
    crash_rate: float = 0.0          # P(client crashes mid-round, no upload)
    net_fail_rate: float = 0.0       # P(one send attempt fails)
    net_retries: int = 3             # client retries before giving up
    net_backoff_s: float = 1.0       # backoff base (doubles per retry)
    duplicate_rate: float = 0.0      # P(payload is replayed to the server)
    bitflip_rate: float = 0.0        # P(one wire bit flips post-seal)
    nan_rate: float = 0.0            # P(client update is NaN/Inf)
    poison_rate: float = 0.0         # P(client ships a norm-inflated update)
    poison_scale: float = 16.0       # poisoned norm = scale * norm bound


@dataclass(frozen=True)
class FedConfig:
    """Cross-device federation scenario knobs (repro.fed).

    The seed orchestrator hard-wired 5 always-on clients in a Python
    loop; these knobs describe the cross-device regimes the federation
    engine simulates: cohort sampling, dropout/stragglers, buffered
    async (FedBuff-style), non-IID hospital silos, and (clock/faults)
    chaos-hardened operation under a simulated wall-clock fault model.
    """

    engine: str = "batched"          # batched (vmapped cohort) | sequential
    # --- fused round execution (fed/engine fused chunks) ---
    # fuse_rounds = S > 1 runs S consecutive sync rounds as ONE jitted
    # lax.scan — train → delta → select → DP → on-device aggregation —
    # with no host round-trip inside the chunk.  Reshape-mode pruning
    # and fedbuff fall back to the per-round path (reshape changes
    # shapes mid-run; fedbuff needs per-round server feedback) while
    # mask-mode pruning (ScbfConfig.prune_impl="mask") runs fused;
    # evaluation coarsens to chunk boundaries (docs/FED_ENGINE.md
    # §Fused round loop / §Pruning on the fused path).
    fuse_rounds: int = 1             # 1 = today's per-round behaviour
    # --- bucketed participant padding (amortise recompiles under
    #     varying per-round P — fed/cohort.bucket_size) ---
    bucket: str = "pow2"             # pow2 (O(log K) compiles) | exact
    # --- pod-axis cohort sharding (fed/engine.BatchedEngine) ---
    pods: int = 1                    # devices on the "pod" mesh axis; 1 = off
    # --- per-round client sampling (sync mode) ---
    sample_fraction: float = 1.0     # fraction of clients invited per round
    dropout_rate: float = 0.0        # P(sampled client never reports back)
    straggler_rate: float = 0.0      # P(client is slow this round)
    drop_stragglers: bool = True     # sync: stragglers miss the deadline
    # --- round scheduling mode ---
    mode: str = "sync"               # sync | fedbuff (buffered async)
    buffer_size: int = 10            # fedbuff: server applies every B uploads
    concurrency: int = 20            # fedbuff: max clients training at once
    staleness_exponent: float = 0.5  # fedbuff weight = (1+tau)^-gamma
    server_lr: float = 1.0           # fedbuff server step on the buffer mean
    # --- data partition across clients ---
    partition: str = "iid"           # iid (equal shards) | dirichlet
    dirichlet_alpha: float = 0.5     # label-skew concentration (lower=worse)
    # --- chaos hardening (repro.fed.clock / repro.fed.faults) ---
    clock: ClockConfig = field(default_factory=ClockConfig)
    faults: FaultConfig = field(default_factory=FaultConfig)
    # server-side admission control (repro.fed.strategy): structural
    # validation, checksum verification and nonfinite rejection are
    # always on; the norm gate turns on with max_update_norm > 0
    max_update_norm: float = 0.0     # L2 bound on an admitted update; 0=off
    norm_action: str = "reject"      # reject | clip (scale into the bound)
    # round-level quorum: fewer than this many participants expected to
    # survive validation triggers a bounded re-plan of the round with
    # backoff instead of stepping on garbage (0 = no quorum)
    min_valid_participants: int = 0
    round_retries: int = 2           # re-plans per round on a quorum miss
    retry_backoff_s: float = 30.0    # simulated wait before each re-plan


@dataclass(frozen=True)
class ObsConfig:
    """Flight-recorder knobs (repro.obs, docs/OBSERVABILITY.md).

    ``device_metrics`` turns on on-device per-round telemetry (loss /
    selected channels / wire bytes accumulated inside the engine
    programs).  It is the only switch for it: an active recorder
    (``obs.trace.recording``) turns on the host event log alone, so a
    recorded run compiles the same programs as an unrecorded one.
    """

    device_metrics: bool = False


@dataclass(frozen=True)
class TrainConfig:
    optimizer: str = "sgd"           # sgd | adam | adamw
    learning_rate: float = 1e-3
    lr_schedule: str = "constant"    # constant | cosine (per global loop)
    weight_decay: float = 0.0
    momentum: float = 0.0
    global_loops: int = 30
    # evaluate AUCROC/AUCPR every N loops (plus always the final loop);
    # non-evaluated loops carry the last-known metrics with
    # LoopRecord.evaluated = False.  Fused execution additionally
    # restricts evaluation to chunk boundaries.
    eval_every: int = 1
    local_epochs: int = 1
    local_batch_size: int = 256
    seed: int = 0
    remat: bool = True
    # debug runs: finite/validity assertions on params and round
    # metrics at chunk boundaries (the SL006-class dynamic net).
    # Host-side checks on already-offloaded values, so the traced
    # program is byte-identical with the flag on or off.
    debug_checks: bool = False
    scbf: ScbfConfig = field(default_factory=ScbfConfig)
    fed: FedConfig = field(default_factory=FedConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)


# ---------------------------------------------------------------------------
# Mesh
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MeshConfig:
    multi_pod: bool = False

    @property
    def shape(self) -> Tuple[int, ...]:
        return (2, 16, 16) if self.multi_pod else (16, 16)

    @property
    def axes(self) -> Tuple[str, ...]:
        return ("pod", "data", "model") if self.multi_pod else ("data", "model")

    @property
    def num_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


# TPU v5e hardware constants for the roofline analysis.
@dataclass(frozen=True)
class HardwareConfig:
    peak_flops: float = 197e12       # bf16 FLOP/s per chip
    hbm_bw: float = 819e9            # bytes/s per chip
    ici_bw: float = 50e9             # bytes/s per link
    hbm_bytes: float = 16e9          # HBM capacity per chip


HARDWARE = HardwareConfig()


def replace(cfg, **kw):
    """dataclasses.replace that works through our frozen configs."""
    return dataclasses.replace(cfg, **kw)
