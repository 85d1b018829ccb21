"""APoZ accumulation kernel + the batched jitted APoZ scorer.

APoZ(neuron j) = (1/B) Σ_b [act[b, j] == 0] over the validation set.
The Pallas kernel counts exact zeros per column of an activation tile
and accumulates int32 counts across the batch grid axis, fusing what the
jnp reference does as compare -> cast -> reduce (three HBM-width passes)
into one resident-tile pass.  Batch streams through the grid so the
validation set never has to fit at once.

``apoz_batch_fractions`` is the scorer the pruning subsystem actually
calls: ONE module-level jitted program (cached per param/batch shape,
never rebuilt per call — the per-call ``jax.jit(lambda ...)`` it
replaces retraced on every pruning step) that runs the MLP activation
pass and reduces each hidden layer to its per-neuron zero fraction.
Mask-mode SCBFwP passes ``neuron_masks`` so pruned neurons read exactly
zero (APoZ 1.0; the planner excludes them), and the fused round loop
calls this same scorer at chunk boundaries — the whole APoZ statistic
is computed on device.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.models.mlp_net import mlp_activations

DEFAULT_BB = 512
DEFAULT_BN = 256


def default_interpret() -> bool:
    """Interpret Pallas kernels unless the default backend is a TPU.

    Read when a kernel is traced, never when a module is imported, so
    the choice follows the backend the program actually runs on.
    """
    return jax.default_backend() != "tpu"


def _apoz_kernel(a_ref, cnt_ref):
    # grid = (column blocks, batch blocks): the batch reduction is the
    # last grid axis, so each output block accumulates over consecutive
    # steps, which is what the compiled pipeline requires
    @pl.when(pl.program_id(1) == 0)
    def _():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    zeros = (a_ref[...] == 0).astype(jnp.int32)
    cnt_ref[...] += jnp.sum(zeros, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("bb", "bn", "interpret"))
def apoz_counts_pallas(acts: jnp.ndarray, bb: int = DEFAULT_BB,
                       bn: int = DEFAULT_BN, interpret: bool | None = None):
    """acts (B, N) -> zero counts (N,) int32.

    ``interpret=None`` picks interpret mode from the backend at trace
    time (``default_interpret``).  The output is one (1, bn) row block
    per column block, so any lane-aligned ``bn`` (or ``bn == N``)
    compiles for the TPU.
    """
    b, n = acts.shape
    assert b % bb == 0 and n % bn == 0, (acts.shape, bb, bn)
    if interpret is None:
        interpret = default_interpret()
    out = pl.pallas_call(
        _apoz_kernel,
        grid=(n // bn, b // bb),
        in_specs=[pl.BlockSpec((bb, bn), lambda j, i: (i, j))],
        out_specs=pl.BlockSpec((1, bn), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(acts)
    return out[0]


def column_block(n: int):
    """Column block width for an N-wide activation, or None if N does
    not tile: DEFAULT_BN when it divides N, the whole width when N is
    narrower (a full-width block needs no lane alignment)."""
    if n % DEFAULT_BN == 0:
        return DEFAULT_BN
    return n if n < DEFAULT_BN else None


def _zero_fraction(act: jnp.ndarray) -> jnp.ndarray:
    """Per-column exact-zero fraction of one (B, N) activation block.

    Dispatches to the Pallas counting kernel when the block tiles
    (count / B equals the jnp mean exactly for any realistic
    validation-set size, so the dispatch never changes the statistic)
    and falls back to the jnp reduction for shapes that do not tile.
    """
    b, n = act.shape
    bn = column_block(n)
    if b % DEFAULT_BB == 0 and bn is not None:
        return apoz_counts_pallas(act, bn=bn).astype(jnp.float32) / b
    return jnp.mean((act == 0.0).astype(jnp.float32), axis=0)


@jax.jit
def apoz_batch_fractions(params, xb, neuron_masks=None):
    """Per-hidden-layer zero fractions of one validation batch.

    The module-level jitted APoZ scorer: jit's shape-keyed cache means
    each (param-geometry, batch, mask) signature compiles exactly once
    per process, however many pruning steps call it.  Streaming callers
    (repro.core.pruning.apoz_scores) accumulate these per-batch
    fractions into the full-set statistic.
    """
    acts = mlp_activations(params, xb, neuron_masks)
    return [_zero_fraction(a) for a in acts]


def apoz_scorer_compile_count() -> int:
    """Compiled-variant count of the batched APoZ scorer (jit cache).

    Reads jit's ``_cache_size`` hook, like
    ``repro.fed.engine.scbf_compile_count``.
    """
    return int(apoz_batch_fractions._cache_size())


def reset_apoz_scorer_compile_count() -> None:
    apoz_batch_fractions._clear_cache()
