"""Cohort execution engines — K local trainings as one XLA program.

The seed orchestrator ran its clients in a sequential Python loop: K
jit dispatches for training, then K eager channel-selection passes,
every global loop.  At cross-device scale (hundreds to thousands of
sampled clients per round) the Python dispatch overhead dominates the
actual math.  ``BatchedEngine`` stacks the sampled clients' shards into
a padded ``(P, n_max, d)`` cohort (repro.fed.cohort) and runs

    local-train  →  delta  →  channel-select  →  (optional DP noise)

for every participant inside a single ``jax.vmap``-ed jit
(``_scbf_pass``), reusing the exact ``lax.scan`` epoch bodies from
``repro.core.client``.  Only the wire encoding (host numpy, it models
bytes crossing the network) remains per-client.

``SequentialEngine`` keeps the seed's per-client loop as the reference
implementation: at full participation with equal shards the two produce
the same trajectories (see tests/test_fed_engine.py), and the gap
between them is what benchmarks/bench_fed_engine.py measures.

Because ``_scbf_pass`` is jitted on shapes, a raw participant axis
would retrace on nearly every round once sampling/dropout make P vary
(cross-silo healthcare FL treats per-round client variability as the
norm).  The engine therefore pads P up to a static *bucket* size
(``repro.fed.cohort.bucket_size``) and threads a per-slot validity mask
through train→delta→select→DP; padded slots compute garbage that the
mask zeroes and ``_emit_payloads`` drops, so valid slots stay
bit-identical to the unbucketed run while ``_scbf_pass`` compiles once
per bucket instead of once per distinct P.

With ``pods > 1`` the bucketed cohort additionally shards across
devices: the slot axis is placed on a 1-D ``("pod",)`` mesh
(launch/mesh.py, pod = federated client axis) and the vmap carries
``spmd_axis_name="pod"`` so one round runs as a single SPMD program —
exercised on CPU via XLA_FLAGS=--xla_force_host_platform_device_count.

Both engines are pure round executors: the driver (repro.core.scbf)
owns PRNG-key derivation, scheduling and aggregation, so an engine swap
can never change the random stream.

**Fused execution** (``FedConfig.fuse_rounds > 1``) goes one step
further: a whole *chunk* of S sync rounds — train → delta → select →
DP → **on-device aggregation** — runs as one jitted ``lax.scan``
(``_fused_scbf_rounds`` / ``_fused_fedavg_rounds``), so nothing crosses
the host inside the chunk.  The driver pre-plans the chunk into static
``(S, B)`` participant/validity arrays (``prepare_fused_plan``, where
every host→device transfer happens), and wire encoding moves off the
critical path: payload bytes are reconstructed from the scan's stacked
``(S, B)`` masked deltas at chunk boundaries (``emit_fused_payloads``),
so ``repro.comm.wire`` remains the single source of truth for upload
accounting.
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass
from functools import lru_cache, partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import wire
from repro.config import ScbfConfig
from repro.core import privacy
from repro.core import selection as sel
from repro.core.client import (client_delta, local_train, local_train_impl,
                               masked_local_train_impl)
from repro.fed.cohort import (PaddedCohort, bucket_size, horizon_slot_plan,
                              pad_clients)
from repro.fed.strategy import fedavg_step, scbf_sum_step
from repro.obs import metrics as obsm
from repro.obs import trace as obstrace


def stack_pytrees(trees: Sequence):
    """Stack a list of identically-shaped pytrees along a new axis 0."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _reveal_masks(masked, masks):
    """Boolean reveal masks shaped exactly like the masked delta.

    ``select_gradients`` reports a mask entry per layer key (``None``
    for bias-free layers); the DP mechanism needs one boolean leaf per
    *transmitted* leaf so noise lands on every revealed coordinate,
    including revealed entries whose gradient is exactly zero.
    """
    return tuple({k: layer_masks[k] for k in layer_delta}
                 for layer_delta, layer_masks in zip(masked, masks))


def _slot_pass(p, x, y, w, lr, ck, sk, dk, v, nm, es=None, *,
               batch_size: int, epochs: int, masked_loss: bool,
               upload_rate: float, selection_mode: str, score_norm: bool,
               dp_noise: float, dp_clip: float, collect: bool = False):
    """Train + delta + channel-select (+ DP) for ONE cohort slot.

    The single traced body shared by the per-round pass and the fused
    chunk scan — sharing it is what keeps the two paths bit-identical.
    ``v`` is the slot-validity bit: padded slots compute garbage that is
    zeroed here (``jnp.where(True, x, 0)`` is ``x`` bitwise, so real
    slots are untouched).  ``nm`` is the optional SCBFwP neuron
    keep-mask tuple (mask-mode pruning): pruned neurons drop out of
    training, selection and DP at static shape; ``None`` traces the
    original unmasked program.

    ``collect=True`` (repro.obs device telemetry) additionally returns
    this slot's ``MetricsCarry`` — the loss comes from the training
    reverse pass (``with_loss``) and the byte/channel counts from the
    already-zeroed ``masked``/``masks``, so the parameter math is
    untouched and stays bit-identical.  ``es`` is the optional
    effective-geometry leaf-size vector (mask-mode SCBFwP byte pricing).
    """
    loss = None
    if masked_loss:
        tr = masked_local_train_impl(p, x, y, w, lr, ck,
                                     batch_size=batch_size,
                                     epochs=epochs, neuron_masks=nm,
                                     with_loss=collect)
    else:
        tr = local_train_impl(p, x, y, lr, ck,
                              batch_size=batch_size, epochs=epochs,
                              neuron_masks=nm, with_loss=collect)
    new_p, loss = tr if collect else (tr, None)
    g = client_delta(p, new_p)
    masked, masks, _ = sel.select_gradients(
        g, upload_rate, selection_mode, key=sk, score_norm=score_norm,
        neuron_masks=nm)
    if dp_noise > 0.0:
        masked = privacy.gaussian_mechanism(
            tuple(masked), dk, dp_noise, dp_clip,
            masks=_reveal_masks(masked, masks))
    masked = tuple({k: jnp.where(v, t, jnp.zeros_like(t))
                    for k, t in layer.items()} for layer in masked)
    masks = tuple({k: (None if m is None else jnp.logical_and(m, v))
                   for k, m in layer.items()} for layer in masks)
    if collect:
        return masked, masks, obsm.slot_metrics(loss, masked, masks, v,
                                                eff_sizes=es)
    return masked, masks


@partial(jax.jit, static_argnames=("batch_size", "epochs", "masked_loss",
                                   "stacked_params", "upload_rate",
                                   "selection_mode", "score_norm",
                                   "dp_noise", "dp_clip", "spmd_axis",
                                   "collect"))
def _scbf_pass(params, xs, ys, ws, lr, ckeys, skeys, dp_keys, valid,
               nmasks=None, eff_sizes=None, *,
               batch_size: int, epochs: int, masked_loss: bool,
               stacked_params: bool, upload_rate: float,
               selection_mode: str, score_norm: bool,
               dp_noise: float, dp_clip: float,
               spmd_axis: Optional[str] = None, collect: bool = False):
    """``_slot_pass`` for B slots in one vmap.

    ``params`` is either one shared pytree (sync rounds) or a B-stacked
    pytree (fedbuff: each participant trains from its own stale
    version).  ``nmasks`` (mask-mode SCBFwP) is one keep-mask tuple
    shared by every slot.  ``spmd_axis`` names the mesh axis the slot
    dimension is sharded over (None = single device).  Returns
    (masked_deltas, masks), both B-stacked — plus the round's reduced
    ``MetricsCarry`` when ``collect`` (``eff_sizes``: shared
    effective-geometry byte pricing, closed over, not vmapped).
    """
    p_ax = 0 if stacked_params else None

    def one(p, x, y, w, ck, sk, dk, v):
        return _slot_pass(p, x, y, w, lr, ck, sk, dk, v, nmasks, eff_sizes,
                          batch_size=batch_size, epochs=epochs,
                          masked_loss=masked_loss, upload_rate=upload_rate,
                          selection_mode=selection_mode,
                          score_norm=score_norm, dp_noise=dp_noise,
                          dp_clip=dp_clip, collect=collect)

    out = jax.vmap(one, in_axes=(p_ax, 0, 0, 0, 0, 0, 0, 0),
                   spmd_axis_name=spmd_axis)(
        params, xs, ys, ws, ckeys, skeys, dp_keys, valid)
    if collect:
        masked, masks, slot_m = out
        return masked, masks, obsm.reduce_slots(slot_m)
    return out


def _fused_scbf_rounds(params, x_all, y_all, w_all, part_idx, valid, admit,
                       lrs, ckeys, skeys, dp_keys, nmasks=None,
                       eff_sizes=None, *, batch_size: int,
                       epochs: int, masked_loss: bool, upload_rate: float,
                       selection_mode: str, score_norm: bool,
                       dp_noise: float, dp_clip: float,
                       spmd_axis: Optional[str] = None,
                       collect: bool = False):
    """S whole SCBF rounds as ONE device program (the fused round loop).

    ``lax.scan`` over the round axis: each step gathers its cohort from
    the device-resident ``(K, n_max, d)`` shards, runs the vmapped
    ``_slot_pass``, and folds the masked deltas into the carried model
    with ``strategy.scbf_sum_step`` — the server apply happens on
    device, with no wire decode and no host round-trip.  All-invalid
    rounds (empty cohorts, tail-chunk padding) pass the carry through
    bitwise untouched because their deltas are zeroed by the validity
    mask.  ``nmasks`` (mask-mode SCBFwP) is the chunk's neuron
    keep-mask tuple — run-constant *within* a chunk (the driver plans
    single-round chunks while pruning is still removing neurons, so a
    chunk never spans a mask update).  Returns
    (new_params, masked_deltas, masks) with the latter two stacked
    ``(S, B, ...)`` for off-critical-path wire encoding — plus the
    ``(S,)``-stacked per-round ``MetricsCarry`` when ``collect``
    (repro.obs device telemetry; the carry rides the scan ys, so the
    parameter math and the host-transfer discipline are untouched).

    ``admit`` is the (S, B) server-admission mask (repro.fed.faults):
    slots the admission gate will reject — corrupted, poisoned, quorum
    casualties — contribute exact zeros to the on-device aggregation
    while their *emitted* deltas stay untouched (the wire artifacts
    must still carry the corrupt bytes for accounting and events).
    Fault-free plans pass admit == valid, and ``jnp.where(True, t, 0)``
    is ``t`` bitwise, so the fault-free trajectory is bit-identical —
    and the program shape never changes, so the <= 2 compile bound
    holds with the fault model active.
    """
    def round_body(p, rnd):
        idx, v, adm, lr, ck, sk, dk = rnd
        xs, ys, ws = x_all[idx], y_all[idx], w_all[idx]

        def one(x, y, w, c, s, d, vv):
            return _slot_pass(p, x, y, w, lr, c, s, d, vv, nmasks,
                              eff_sizes,
                              batch_size=batch_size, epochs=epochs,
                              masked_loss=masked_loss,
                              upload_rate=upload_rate,
                              selection_mode=selection_mode,
                              score_norm=score_norm, dp_noise=dp_noise,
                              dp_clip=dp_clip, collect=collect)

        out = jax.vmap(one, in_axes=(0, 0, 0, 0, 0, 0, 0),
                       spmd_axis_name=spmd_axis)(
            xs, ys, ws, ck, sk, dk, v)
        if collect:
            masked, masks, slot_m = out
            ys_out = (masked, masks, obsm.reduce_slots(slot_m))
        else:
            masked, masks = out
            ys_out = (masked, masks)
        admitted = tuple(
            {k: jnp.where(adm.reshape(adm.shape + (1,) * (t.ndim - 1)),
                          t, jnp.zeros_like(t))
             for k, t in layer.items()} for layer in masked)
        return scbf_sum_step(p, admitted, neuron_masks=nmasks), ys_out

    new_p, ys_s = jax.lax.scan(
        round_body, tuple(params),
        (part_idx, valid, admit, lrs, ckeys, skeys, dp_keys))
    if collect:
        masked_s, masks_s, met_s = ys_s
        return new_p, masked_s, masks_s, met_s
    masked_s, masks_s = ys_s
    return new_p, masked_s, masks_s


def _fused_fedavg_rounds(params, x_all, y_all, w_all, part_idx, weights,
                         lrs, ckeys, *, batch_size: int, epochs: int,
                         masked_loss: bool,
                         spmd_axis: Optional[str] = None,
                         collect: bool = False):
    """S whole FedAvg rounds as one device program.

    Like ``_fused_scbf_rounds`` but full-weight: each scan step trains
    the cohort and replaces the carry with the example-weighted mean
    (``strategy.fedavg_step``; ``weights`` carries exact zeros on
    invalid slots, and an all-zero round keeps the carry unchanged).
    FedAvg ships dense weights, so nothing per-round needs to reach the
    host — only the final model is returned, plus the ``(S,)``-stacked
    ``FedAvgMetrics`` (loss / participant counts, slot validity derived
    from the zero-weight convention) when ``collect``.
    """
    def round_body(p, rnd):
        idx, wts, lr, ck = rnd
        xs, ys, ws = x_all[idx], y_all[idx], w_all[idx]

        def one(x, y, w, k):
            if masked_loss:
                return masked_local_train_impl(p, x, y, w, lr, k,
                                               batch_size=batch_size,
                                               epochs=epochs,
                                               with_loss=collect)
            return local_train_impl(p, x, y, lr, k,
                                    batch_size=batch_size, epochs=epochs,
                                    with_loss=collect)

        out = jax.vmap(one, in_axes=(0, 0, 0, 0),
                       spmd_axis_name=spmd_axis)(xs, ys, ws, ck)
        if collect:
            new_stack, losses = out
            valid = wts > 0.0
            met = obsm.FedAvgMetrics(
                loss_sum=jnp.sum(jnp.where(valid, losses, 0.0)
                                 ).astype(jnp.float32),
                participants=jnp.sum(valid.astype(jnp.int32)))
        else:
            new_stack, met = out, None
        return fedavg_step(p, new_stack, wts), met

    new_p, met_s = jax.lax.scan(round_body, tuple(params),
                                (part_idx, weights, lrs, ckeys))
    if collect:
        return new_p, met_s
    return new_p


@lru_cache(maxsize=None)
def _fused_programs():
    """The jitted fused-chunk programs, built on first use.

    The model carry is buffer-donated into the chunk call on backends
    that support donation (CPU ignores it, with a warning per compile)
    — and deciding that requires querying the backend, which
    *initializes* it.  Building the jits lazily keeps importing this
    module free of backend side effects: XLA_FLAGS / JAX_PLATFORMS set
    after import but before first use still take effect.
    """
    donate = (0,) if jax.default_backend() != "cpu" else ()
    scbf = partial(jax.jit,
                   static_argnames=("batch_size", "epochs", "masked_loss",
                                    "upload_rate", "selection_mode",
                                    "score_norm", "dp_noise", "dp_clip",
                                    "spmd_axis", "collect"),
                   donate_argnums=donate)(_fused_scbf_rounds)
    fedavg = partial(jax.jit,
                     static_argnames=("batch_size", "epochs", "masked_loss",
                                      "spmd_axis", "collect"),
                     donate_argnums=donate)(_fused_fedavg_rounds)
    return scbf, fedavg


@partial(jax.jit, static_argnames=("batch_size", "epochs", "masked_loss",
                                   "spmd_axis", "collect"))
def _fedavg_pass(params, xs, ys, ws, lr, ckeys, *,
                 batch_size: int, epochs: int, masked_loss: bool,
                 spmd_axis: Optional[str] = None, collect: bool = False):
    """Full-weight local training for B slots in one vmap.

    Padded slots need no validity gating here: their trained params are
    per-slot outputs that ``fedavg_round`` simply never reads (and with
    ``collect`` the caller slices the loss vector to real slots).
    """
    def one(p, x, y, w, ck):
        if masked_loss:
            return masked_local_train_impl(p, x, y, w, lr, ck,
                                           batch_size=batch_size,
                                           epochs=epochs,
                                           with_loss=collect)
        return local_train_impl(p, x, y, lr, ck,
                                batch_size=batch_size, epochs=epochs,
                                with_loss=collect)

    return jax.vmap(one, in_axes=(None, 0, 0, 0, 0),
                    spmd_axis_name=spmd_axis)(params, xs, ys, ws, ckeys)


def _compact_layers(layers, keep):
    """Host-side effective-geometry slicing of one slot's layer dicts.

    Mask-mode SCBFwP emission: ``keep[l]`` are the kept neuron ids of
    hidden layer l, and the sliced arrays are exactly what
    ``pruning.apply_structure`` would have produced — so wire encoding
    (bytes, bitmap sizes, dense reference) and mask accounting see the
    *effective* model, matching what a physically-compacted run ships.
    ``None`` leaves (bias-free masks) pass through.
    """
    out = []
    prev = None
    last = len(layers) - 1
    for l, layer in enumerate(layers):
        new = {}
        for kk, vv in layer.items():
            if vv is None:
                new[kk] = None
                continue
            a = np.asarray(vv)
            if kk == "w":
                if prev is not None:
                    a = a[prev]
                if l < last:
                    a = a[:, keep[l]]
            elif l < last:
                a = a[keep[l]]
            new[kk] = a
        if l < last:
            prev = keep[l]
        out.append(new)
    return tuple(out)


def _encode_slot(masked_host, masks_host, sl, keep=None):
    """Wire-encode one slot of a host-side stacked pass output.

    ``sl`` indexes the stacked leading axes — ``(i,)`` for a per-round
    pass, ``(r, i)`` for a fused chunk — so both paths share the exact
    same encode + accounting code (``repro.comm.wire`` stays the single
    source of truth for upload bytes).  ``keep`` (mask-mode SCBFwP)
    compacts the slot to its effective geometry before encoding.
    """
    mg = tuple({kk: vv[sl] for kk, vv in layer.items()}
               for layer in masked_host)
    mk = tuple({kk: (None if vv is None else vv[sl])
                for kk, vv in layer.items()} for layer in masks_host)
    if keep is not None:
        mg = _compact_layers(mg, keep)
        mk = _compact_layers(mk, keep)
    return wire.encode(mg), sel.UploadStats.from_masks(mk)


def _pull_and_encode(masked_stacked, masks_stacked, slots, keep=None
                     ) -> Tuple[List[wire.Payload], List[sel.UploadStats]]:
    """Wait for the device, pull both stacks to the host in one
    transfer, then wire-encode every slot in ``slots`` (index tuples
    into the stacked leading axes, see ``_encode_slot``).

    The three stretches are spans of their own — ``emit_wait`` (the host
    blocked on the device work that produced the stacks), ``emit_pull``
    (the device→host copy, ``bytes`` pulled) and ``wire_encode`` (the
    NumPy encoding) — shared by the per-round and the fused emitters.
    The wait adds no synchronisation: the pull waits for the same
    results.
    """
    stacks = (masked_stacked, masks_stacked)
    with obstrace.span("emit_wait"):
        jax.block_until_ready(stacks)
    pulled = sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(stacks))
    with obstrace.span("emit_pull", bytes=pulled):
        masked_host, masks_host = jax.device_get(stacks)
    payloads, stats = [], []
    with obstrace.span("wire_encode", slots=len(slots)):
        for sl in slots:
            payload, st = _encode_slot(masked_host, masks_host, sl, keep)
            payloads.append(payload)
            stats.append(st)
    return payloads, stats


def _emit_payloads(masked_stacked, masks_stacked, num: int, keep=None
                   ) -> Tuple[List[wire.Payload], List[sel.UploadStats]]:
    """One device→host transfer, then per-client wire encoding.

    ``num`` is the real participant count P: slots P..B-1 of a bucketed
    pass are padding (already zeroed by the validity mask) and are never
    encoded — padded slots ship zero bytes.
    """
    with obstrace.span("encode", clients=num):
        return _pull_and_encode(masked_stacked, masks_stacked,
                                [(i,) for i in range(num)], keep)


def _host_round_metrics(payloads, stats, losses):
    """Sequential-path round telemetry, same dict shape as
    ``obsm.offload``.

    The reference engine already has everything on the host, so its
    numbers come straight from the encoded payloads (``repro.comm.wire``
    stays the byte source of truth) instead of a device carry.
    """
    return {
        "participants": len(payloads),
        "train_loss": (sum(losses) / len(losses)) if losses else 0.0,
        "sparse_bytes": int(sum(p.nbytes for p in payloads)),
        "codec_bytes": wire.codec_breakdown(payloads),
    }


def _host_fedavg_metrics(losses, num: int):
    """Sequential fedavg round telemetry: cohort-level aggregates only.

    The per-client loss list is reduced to its cohort mean HERE, before
    the dict crosses into ``LoopRecord``/events.jsonl — this function is
    a declared aggregation point in the privlint policy
    (repro.analysis.privrules), so per-client scalars must not be added
    to the dict.
    """
    return {
        "participants": num,
        "train_loss": (sum(losses) / len(losses)) if losses else 0.0,
    }


@dataclass
class FusedPlan:
    """Device-resident plan for one fused chunk of rounds.

    Built by ``BatchedEngine.prepare_fused_plan`` — every host→device
    transfer for the chunk happens there, so the chunk execution itself
    is transfer-free (provable under ``jax.transfer_guard("disallow")``,
    see tests/test_fused_rounds.py).
    """

    rounds: int                       # real rounds in the chunk (<= S)
    num_slots: int                    # B, constant across the whole run
    participants: List[np.ndarray]    # per real round (host ids)
    part_idx: jnp.ndarray             # (S, B) int32 cohort gather indices
    valid: jnp.ndarray                # (S, B) bool slot validity
    lrs: jnp.ndarray                  # (S,) float32 lr table slice
    ckeys: jnp.ndarray                # (S, B, 2) per-slot training keys
    skeys: jnp.ndarray                # (S, B, 2) selection keys
    dp_keys: jnp.ndarray              # (S, B, 2) DP noise keys
    weights: Optional[jnp.ndarray] = None   # (S, B) f32 — fedavg only
    eff_sizes: Optional[jnp.ndarray] = None  # (n_leaves,) i32 — obs byte
    # pricing under mask-mode SCBFwP (device-placed at plan build so the
    # chunk stays transfer-free); None prices full leaf sizes statically
    admit: Optional[jnp.ndarray] = None     # (S, B) bool server admission
    # mask (repro.fed.faults) — None means admit == valid (no faults)


def _pad_slots(arr, num_slots: int):
    """Pad axis 0 up to ``num_slots`` by repeating slot 0.

    Slot-0 content (not zeros) keeps padded slots numerically
    well-behaved — they train on a real shard, and everything they
    produce is zeroed by the validity mask and dropped before encoding.
    """
    p = arr.shape[0]
    if num_slots == p:
        return arr
    reps = jnp.broadcast_to(arr[:1], (num_slots - p,) + arr.shape[1:])
    return jnp.concatenate([jnp.asarray(arr), reps], axis=0)


def _pad_key_slots(keys, num_slots: int):
    """Pad a (P, 2) PRNG key row up to ``num_slots`` with *distinct*
    filler keys.

    Padded slots are validity-masked — nothing they produce survives —
    but repeating slot 0's key verbatim would make every padded slot
    draw slot 0's noise stream (privlint PL003); offsetting the second
    key word keeps each slot's stream distinct at zero cost, and the
    validity mask still guarantees bit-identical round outputs.
    """
    keys = jnp.asarray(keys)
    p = keys.shape[0]
    if num_slots == p:
        return keys
    pad = num_slots - p
    offs = jnp.stack([jnp.zeros(pad, jnp.uint32),
                      jnp.arange(1, pad + 1, dtype=jnp.uint32)], axis=1)
    return jnp.concatenate([keys, keys[:1] + offs], axis=0)


class BatchedEngine:
    """Vmapped bucketed-cohort execution: one XLA program per round.

    ``bucket`` picks the participant-padding policy
    (repro.fed.cohort.bucket_size); ``pods > 1`` shards the bucketed
    slot axis over a 1-D pod mesh so the round runs SPMD across
    devices.
    """

    name = "batched"

    def __init__(self, clients: Sequence[Tuple[np.ndarray, np.ndarray]],
                 batch_size: int, epochs: int, bucket: str = "pow2",
                 pods: int = 1):
        # validate the policy at construction, not on round 1
        bucket_size(1, 1, bucket)
        self.cohort: PaddedCohort = pad_clients(clients)
        self.counts = self.cohort.counts
        self.batch_size = batch_size
        self.epochs = epochs
        self.bucket = bucket
        self.pods = max(1, int(pods))
        if self.pods > 1:
            from repro.launch.mesh import make_pod_mesh
            from repro.sharding.rules import (cohort_shardings,
                                              fused_plan_shardings)
            self.mesh = make_pod_mesh(self.pods)
            self._slot_sharding, self._repl_sharding = \
                cohort_shardings(self.mesh)
            self._fused_slot_sharding, _ = fused_plan_shardings(self.mesh)
            from repro.sharding.rules import keep_mask_sharding
            self._mask_sharding = keep_mask_sharding(self.mesh)
        else:
            self.mesh = None
        self._cohort_replicated = False

    @property
    def num_clients(self) -> int:
        return self.cohort.num_clients

    @property
    def spmd_axis(self) -> Optional[str]:
        return "pod" if self.mesh is not None else None

    def _mesh_ctx(self):
        # jax.set_mesh (not the legacy ``with mesh:``) is what makes the
        # pod axis visible to vmap's spmd_axis_name
        return jax.set_mesh(self.mesh) if self.mesh is not None else \
            contextlib.nullcontext()

    def _gather(self, participants: np.ndarray):
        part = np.asarray(participants)
        if part.size == self.num_clients and \
                np.array_equal(part, np.arange(self.num_clients)):
            return self.cohort.x, self.cohort.y, self.cohort.w
        return self.cohort.x[part], self.cohort.y[part], self.cohort.w[part]

    def round_slots(self, num_participants: int) -> int:
        """Slots a per-round program trains for ``num_participants``
        reporters: their bucket (0 for an empty round, which dispatches
        nothing)."""
        return bucket_size(num_participants, self.num_clients, self.bucket,
                           self.pods)

    def _bucketed_inputs(self, participants, slot_arrays, key_arrays=(),
                         params=None):
        """Pad per-slot arrays up to the bucket; returns (B, arrays,
        keys, params, valid).  Data arrays pad by repeating slot 0
        (``_pad_slots``); PRNG key rows pad with distinct derived keys
        (``_pad_key_slots``) so padded slots never share a noise stream.
        With a pod mesh, per-slot arrays are placed with the slot axis
        sharded over ``pod`` and params replicated.
        """
        p_count = len(participants)
        b = self.round_slots(p_count)
        valid = jnp.arange(b) < p_count
        out = [_pad_slots(jnp.asarray(a), b) for a in slot_arrays]
        keys = [_pad_key_slots(k, b) for k in key_arrays]
        if params is not None:
            params = jax.tree_util.tree_map(lambda l: _pad_slots(l, b),
                                            params)
        if self.mesh is not None:
            out = [jax.device_put(a, self._slot_sharding) for a in out]
            keys = [jax.device_put(k, self._slot_sharding) for k in keys]
            valid = jax.device_put(valid, self._slot_sharding)
            if params is not None:
                params = jax.device_put(params, self._slot_sharding)
        return b, out, keys, params, valid

    def scbf_round(self, params, participants, lr, ckeys, skeys, dp_keys,
                   cfg: ScbfConfig, nmasks=None, keep=None,
                   collect: bool = False):
        """Masked sparse uploads for every participant, one batched pass.

        ``params``: one pytree (sync) or a list of per-participant
        pytrees (fedbuff stale versions).  ``nmasks``/``keep`` are the
        mask-mode SCBFwP neuron keep-masks (device tuple threaded into
        the pass) and kept-index sets (host, for effective-geometry
        emission).  An empty round returns ``([], [])`` without
        dispatching a P=0 program.  ``collect`` (repro.obs) appends the
        round's offloaded device-telemetry dict to the return tuple.
        """
        p_count = len(participants)
        if not p_count:
            return ([], [], None) if collect else ([], [])
        xs, ys, ws = self._gather(participants)
        stacked = isinstance(params, list)
        p = stack_pytrees(params) if stacked else tuple(params)
        _, (xs, ys, ws), (ck, sk, dk), p_stk, valid = \
            self._bucketed_inputs(
                participants, (xs, ys, ws),
                key_arrays=(jnp.stack(list(ckeys)),
                            jnp.stack(list(skeys)),
                            jnp.stack(list(dp_keys))),
                params=p if stacked else None)
        if stacked:
            p = p_stk
        elif self.mesh is not None:
            p = jax.device_put(p, self._repl_sharding)
        if nmasks is not None and self.mesh is not None:
            nmasks = jax.device_put(tuple(nmasks), self._mask_sharding)
        eff = None
        if collect and keep is not None:
            ref = params[0] if stacked else params
            eff = jnp.asarray(obsm.effective_leaf_sizes(ref, keep))
        with self._mesh_ctx():
            out = _scbf_pass(
                p, xs, ys, ws, lr, ck, sk, dk, valid, nmasks, eff,
                batch_size=self.batch_size, epochs=self.epochs,
                masked_loss=not self.cohort.uniform, stacked_params=stacked,
                upload_rate=cfg.upload_rate, selection_mode=cfg.selection,
                score_norm=cfg.score_norm, dp_noise=cfg.dp_noise_multiplier,
                dp_clip=cfg.dp_clip_norm, spmd_axis=self.spmd_axis,
                collect=collect)
        if collect:
            masked, masks, met = out
            payloads, stats = _emit_payloads(masked, masks, p_count, keep)
            return payloads, stats, obsm.offload(met)
        masked, masks = out
        return _emit_payloads(masked, masks, p_count, keep)

    def fedavg_round(self, params, participants, lr, ckeys,
                     collect: bool = False):
        """Full-weight training; returns (per-client params list, counts).

        Training runs stacked in one vmap; the returned list holds
        per-client views into that output so the aggregation strategy
        can reduce incrementally (core.server.fedavg_update).  Padded
        bucket slots are simply never read.  ``collect`` appends the
        loss-only device-telemetry dict.
        """
        p_count = len(participants)
        if not p_count:
            return ([], self.counts[:0], None) if collect \
                else ([], self.counts[:0])
        xs, ys, ws = self._gather(participants)
        p = tuple(params)
        _, (xs, ys, ws), (ck,), _, valid = self._bucketed_inputs(
            participants, (xs, ys, ws),
            key_arrays=(jnp.stack(list(ckeys)),))
        if self.mesh is not None:
            p = jax.device_put(p, self._repl_sharding)
        with self._mesh_ctx():
            out = _fedavg_pass(p, xs, ys, ws, lr, ck,
                               batch_size=self.batch_size,
                               epochs=self.epochs,
                               masked_loss=not self.cohort.uniform,
                               spmd_axis=self.spmd_axis, collect=collect)
        if collect:
            new_p, losses = out
            # same validity-masked accounting as the fused path's
            # round_body: padded tail slots (real losses, trained on
            # slot 0's shard under distinct filler keys) are excluded
            # by mask rather than by slicing — bit-identical to the
            # old sliced sum, since adding the masked zeros cannot
            # move an f32 sum of finite values
            met = obsm.FedAvgMetrics(
                loss_sum=jnp.sum(jnp.where(valid, losses, 0.0)
                                 ).astype(jnp.float32),
                participants=jnp.sum(valid.astype(jnp.int32)))
            dm = obsm.offload(met)
        else:
            new_p = out
        res = [jax.tree_util.tree_map(lambda l, i=i: l[i], new_p)
               for i in range(p_count)]
        counts = self.counts[np.asarray(participants)]
        return (res, counts, dm) if collect else (res, counts)

    # ------------------------------------------------------------------
    # fused execution: S whole rounds per device program
    # ------------------------------------------------------------------

    def fused_num_slots(self, max_participants: int) -> int:
        """The run-constant slot count B for fused chunks.

        Sized to the scheduler's worst-case cohort (not per-round
        buckets): every chunk of the run then shares ONE compiled
        program, which is what keeps the fused path at <= 2 compiles
        across an arbitrarily-varying participation trace.
        """
        return bucket_size(max_participants, self.num_clients, self.bucket,
                           self.pods)

    def prepare_fused_plan(self, participants: Sequence[np.ndarray],
                           lrs: Sequence[float],
                           ckeys: Sequence, skeys: Sequence,
                           dp_keys: Sequence, horizon: int,
                           num_slots: int, weights=None,
                           eff_sizes=None, admit=None) -> FusedPlan:
        """Assemble + device-place one chunk's static (S, B) plan.

        Per-round key rows pad with distinct derived keys and a short
        tail chunk pads with all-invalid rounds, exactly mirroring the
        per-round path's ``_pad_slots``/``_pad_key_slots`` semantics —
        this is where every host→device transfer for the chunk happens.
        ``admit`` (repro.fed.faults): per-round (P,) bool admission
        rows; None admits every valid slot (the fault-free plan).
        """
        if self.mesh is not None and not self._cohort_replicated:
            # fused chunks gather cohorts on device, so the shards must
            # live replicated across the mesh (weights-never-shard-over-
            # pod applies to data here too: pod splits the *slot* axis).
            # Deferred to first fused use — per-round pod runs re-gather
            # and re-shard per round and never need the replicas.
            self.cohort = PaddedCohort(
                jax.device_put(self.cohort.x, self._repl_sharding),
                jax.device_put(self.cohort.y, self._repl_sharding),
                jax.device_put(self.cohort.w, self._repl_sharding),
                self.cohort.counts)
            self._cohort_replicated = True
        parts = [np.asarray(p) for p in participants]
        part_idx, valid = horizon_slot_plan(parts, num_slots, horizon)

        def pad_rows(rows, trailing):
            out = np.zeros((horizon, num_slots) + trailing, np.uint32)
            for r, k in enumerate(rows):
                k = np.asarray(k)
                if k.shape[0]:
                    out[r, :k.shape[0]] = k
                    pad = num_slots - k.shape[0]
                    if pad:
                        # distinct filler keys, mirroring _pad_key_slots:
                        # padded slots are validity-masked but must not
                        # share slot 0's noise stream (privlint PL003)
                        offs = np.zeros((pad,) + trailing, np.uint32)
                        offs[..., -1] = np.arange(1, pad + 1,
                                                  dtype=np.uint32)
                        out[r, k.shape[0]:] = k[0] + offs
            return out

        lr_arr = np.zeros(horizon, np.float32)
        lr_arr[:len(list(lrs))] = np.asarray(list(lrs), np.float32)
        wts = None
        if weights is not None:
            wts = np.zeros((horizon, num_slots), np.float32)
            for r, w in enumerate(weights):
                w = np.asarray(w, np.float32)
                wts[r, :w.shape[0]] = w

        if admit is None:
            admit_arr = np.asarray(valid, dtype=bool)
        else:
            admit_arr = np.zeros((horizon, num_slots), dtype=bool)
            for r, row in enumerate(admit):
                row = np.asarray(row, dtype=bool)
                admit_arr[r, :row.shape[0]] = row

        key_dim = (2,)
        arrs = {
            "part_idx": part_idx, "valid": valid, "admit": admit_arr,
            "ckeys": pad_rows(ckeys, key_dim),
            "skeys": pad_rows(skeys, key_dim),
            "dp_keys": pad_rows(dp_keys, key_dim),
        }
        if self.mesh is not None:
            dev = {k: jax.device_put(jnp.asarray(v),
                                     self._fused_slot_sharding)
                   for k, v in arrs.items()}
            lr_dev = jax.device_put(jnp.asarray(lr_arr),
                                    self._repl_sharding)
            wts_dev = None if wts is None else \
                jax.device_put(jnp.asarray(wts), self._fused_slot_sharding)
            eff_dev = None if eff_sizes is None else jax.device_put(
                jnp.asarray(eff_sizes, jnp.int32), self._repl_sharding)
        else:
            dev = {k: jnp.asarray(v) for k, v in arrs.items()}
            lr_dev = jnp.asarray(lr_arr)
            wts_dev = None if wts is None else jnp.asarray(wts)
            eff_dev = None if eff_sizes is None else \
                jnp.asarray(eff_sizes, jnp.int32)
        return FusedPlan(rounds=len(parts), num_slots=num_slots,
                         participants=parts, part_idx=dev["part_idx"],
                         valid=dev["valid"], lrs=lr_dev,
                         ckeys=dev["ckeys"], skeys=dev["skeys"],
                         dp_keys=dev["dp_keys"], weights=wts_dev,
                         eff_sizes=eff_dev, admit=dev["admit"])

    def fused_scbf_chunk(self, params, plan: FusedPlan, cfg: ScbfConfig,
                         nmasks=None, collect: bool = False):
        """Run one fused chunk: S rounds, zero host crossings inside.

        ``nmasks`` (mask-mode SCBFwP) is the chunk's neuron keep-mask
        tuple — device arrays, replicated across a pod mesh (keep-masks
        are model-geometry state and follow the weights-never-shard
        contract).  Returns (new_params, masked_deltas, masks) — the
        stacked outputs stay on device until ``emit_fused_payloads``
        pulls them for wire accounting at the chunk boundary — plus the
        (S,)-stacked on-device ``MetricsCarry`` when ``collect`` (the
        caller offloads it together with the payload transfer; nothing
        extra crosses the host inside the chunk).
        """
        p = tuple(params)
        if self.mesh is not None:
            p = jax.device_put(p, self._repl_sharding)
            if nmasks is not None:
                nmasks = jax.device_put(tuple(nmasks), self._mask_sharding)
        fused_scbf, _ = _fused_programs()
        admit = plan.admit if plan.admit is not None else plan.valid
        with self._mesh_ctx():
            return fused_scbf(
                p, self.cohort.x, self.cohort.y, self.cohort.w,
                plan.part_idx, plan.valid, admit, plan.lrs,
                plan.ckeys, plan.skeys, plan.dp_keys, nmasks,
                plan.eff_sizes,
                batch_size=self.batch_size, epochs=self.epochs,
                masked_loss=not self.cohort.uniform,
                upload_rate=cfg.upload_rate, selection_mode=cfg.selection,
                score_norm=cfg.score_norm,
                dp_noise=cfg.dp_noise_multiplier,
                dp_clip=cfg.dp_clip_norm, spmd_axis=self.spmd_axis,
                collect=collect)

    def fused_fedavg_chunk(self, params, plan: FusedPlan,
                           collect: bool = False):
        """Run one fused FedAvg chunk; returns only the final params
        (plus the (S,)-stacked ``FedAvgMetrics`` when ``collect``)."""
        if plan.weights is None:
            raise ValueError("fused fedavg needs the plan built with "
                             "per-slot example weights")
        p = tuple(params)
        if self.mesh is not None:
            p = jax.device_put(p, self._repl_sharding)
        _, fused_fedavg = _fused_programs()
        with self._mesh_ctx():
            return fused_fedavg(
                p, self.cohort.x, self.cohort.y, self.cohort.w,
                plan.part_idx, plan.weights, plan.lrs, plan.ckeys,
                batch_size=self.batch_size, epochs=self.epochs,
                masked_loss=not self.cohort.uniform,
                spmd_axis=self.spmd_axis, collect=collect)

    def emit_fused_payloads(self, masked_s, masks_s, plan: FusedPlan,
                            keep=None
                            ) -> List[Tuple[List[wire.Payload],
                                            List[sel.UploadStats]]]:
        """One device→host transfer for the whole chunk, then per-round
        wire encoding off the critical path.

        ``keep`` (mask-mode SCBFwP) compacts every slot to the
        effective geometry before encoding, so the reported bytes are
        what a physically-pruned model would ship.  Returns
        ``[(payloads, stats), ...]`` per *real* round; padding rounds
        and padded slots are never encoded and ship zero bytes.  The
        reconstructed payloads are byte-identical to what the per-round
        path emits because the masked deltas are.
        """
        sizes = [int(part.size) for part in plan.participants]
        with obstrace.span("encode", rounds=plan.rounds):
            payloads, stats = _pull_and_encode(
                masked_s, masks_s,
                [(r, i) for r, n in enumerate(sizes) for i in range(n)],
                keep)
        out, at = [], 0
        for n in sizes:
            out.append((payloads[at:at + n], stats[at:at + n]))
            at += n
        return out


class SequentialEngine:
    """The seed's per-client Python loop, kept as the reference path.

    Bucketing is a batched-engine concept (there is no shared program
    to retrace here), so ``bucket`` is accepted-and-ignored for
    signature parity; ``pods > 1`` is refused — the loop is inherently
    single-device.
    """

    name = "sequential"

    def __init__(self, clients: Sequence[Tuple[np.ndarray, np.ndarray]],
                 batch_size: int, epochs: int, bucket: str = "pow2",
                 pods: int = 1):
        if pods > 1:
            raise ValueError("the sequential engine is single-device; "
                             "pod sharding needs engine='batched'")
        self.clients = [(jnp.asarray(x), jnp.asarray(y)) for x, y in clients]
        self.counts = np.array([x.shape[0] for x, _ in clients],
                               dtype=np.int64)
        self.batch_size = batch_size
        self.epochs = epochs

    @property
    def num_clients(self) -> int:
        return len(self.clients)

    def round_slots(self, num_participants: int) -> int:
        """One program per participant: no padded slot."""
        return num_participants

    def scbf_round(self, params, participants, lr, ckeys, skeys, dp_keys,
                   cfg: ScbfConfig, nmasks=None, keep=None,
                   collect: bool = False):
        stacked = isinstance(params, list)
        payloads, stats = [], []
        losses = []
        for i, k in enumerate(participants):
            p0 = tuple(params[i]) if stacked else tuple(params)
            xc, yc = self.clients[int(k)]
            tr = local_train(p0, xc, yc, lr, ckeys[i],
                             batch_size=self.batch_size,
                             epochs=self.epochs, neuron_masks=nmasks,
                             with_loss=collect)
            new_p, loss = tr if collect else (tr, None)
            if collect:
                losses.append(loss)          # device scalar; fetched once below
            g = client_delta(p0, new_p)
            masked, masks, _ = sel.select_gradients(
                g, cfg.upload_rate, cfg.selection, key=skeys[i],
                score_norm=cfg.score_norm, neuron_masks=nmasks)
            if cfg.dp_noise_multiplier > 0.0:
                masked = privacy.gaussian_mechanism(
                    tuple(masked), dp_keys[i], cfg.dp_noise_multiplier,
                    cfg.dp_clip_norm, masks=_reveal_masks(masked, masks))
            masked, masks = tuple(masked), tuple(masks)
            if keep is not None:
                masked = _compact_layers(masked, keep)
                masks = _compact_layers(masks, keep)
            payloads.append(wire.encode(masked))
            stats.append(sel.UploadStats.from_masks(masks))
        if collect:
            losses = [float(x) for x in jax.device_get(losses)]
            return payloads, stats, _host_round_metrics(payloads, stats,
                                                        losses)
        return payloads, stats

    def fedavg_round(self, params, participants, lr, ckeys,
                     collect: bool = False):
        outs = []
        losses = []
        for i, k in enumerate(participants):
            xc, yc = self.clients[int(k)]
            tr = local_train(tuple(params), xc, yc, lr, ckeys[i],
                             batch_size=self.batch_size,
                             epochs=self.epochs, with_loss=collect)
            new_p, loss = tr if collect else (tr, None)
            if collect:
                losses.append(loss)          # device scalar; fetched once below
            outs.append(new_p)
        counts = self.counts[np.asarray(participants)]
        if collect:
            losses = [float(x) for x in jax.device_get(losses)]
            return outs, counts, _host_fedavg_metrics(losses, len(outs))
        return outs, counts


ENGINES = {"batched": BatchedEngine, "sequential": SequentialEngine}


def scbf_compile_count() -> int:
    """Compiled-variant count of the batched SCBF pass (jit cache size).

    One entry per traced (shape, static-args) combination — the number
    tests and benchmarks assert stays at "one per bucket", not "one per
    distinct P" (clear with ``reset_scbf_compile_count`` first).  Reads
    jit's ``_cache_size`` hook: ``jax.monitoring`` compile events are
    process-global, and there is no public per-function count.
    """
    return int(_scbf_pass._cache_size())


def reset_scbf_compile_count() -> None:
    _scbf_pass._clear_cache()


def fused_compile_count() -> int:
    """Compiled-variant count of the fused chunk programs (jit cache).

    The fused acceptance bar is "<= 2 compiles across a varying-P
    trace": because the plan is padded to a run-constant (S, B), every
    chunk — including the short tail — shares one compiled program.
    """
    scbf, fedavg = _fused_programs()
    return int(scbf._cache_size() + fedavg._cache_size())


def reset_fused_compile_count() -> None:
    scbf, fedavg = _fused_programs()
    scbf._clear_cache()
    fedavg._clear_cache()


def make_engine(kind: str, clients, batch_size: int, epochs: int,
                bucket: str = "pow2", pods: int = 1):
    if kind not in ENGINES:
        raise ValueError(f"unknown engine {kind!r}; one of {sorted(ENGINES)}")
    return ENGINES[kind](clients, batch_size, epochs, bucket=bucket,
                         pods=pods)
