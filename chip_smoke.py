"""Smoke run of the paper's federated MLP on a TPU.

    python chip_smoke.py            # one chip: every single-device phase
    python chip_smoke.py --pods 4   # four chips: the pod-sharded phase only

Drives ``repro.core.scbf.run_federated`` (the function behind
``python -m repro.launch.train --mode medical``) at the full width the
repository supports: the synthetic 30,760 x 2,917 cohort, K=5 IID
hospitals, the MLP (2917, 256, 64, 1), local batch 256, 2 local epochs.
Weights and data come from a fixed seed.

Phases, all in this one process:

  scbf         per-round SCBF (batched engine), 3 loops
  fedavg       per-round FedAvg, 3 loops
  fused_scbf   SCBF with fuse_rounds=3 over the same 3 loops; final
               params agree with ``scbf`` within PARAM_TOL, <= 2 fused
               compiles
  fused_scbfwp mask-mode SCBFwP, fused, >= 2 pruning steps, with the
               APoZ scorer's Pallas kernel compiled (not interpreted)
  apoz_kernel  compiled APoZ counts == jnp zero counts, exactly
  pods         (--pods N only) fused SCBF sharded over N chips against
               the same plan on one chip, within PARAM_TOL

Every run checks finite params and losses, AUC-ROC above 0.5 and, for
SCBF, sparse_bytes <= dense_bytes.  Each run happens twice: the first
call includes compilation, the second is steady state.  The times
printed are from this one smoke run, not a benchmark.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Without a
TPU, or when any phase fails, the script exits non-zero and prints no
such line.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
import traceback
from dataclasses import dataclass
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Float32 matmuls run at the TPU's default precision, and channel
# selection thresholds on quantiles of the deltas, so paths that differ
# in program shape (fused scan vs per-round, 4 chips vs 1) need not be
# bit-identical on the chip (they are on the CPU).  They must agree to
# within PARAM_TOL of how far training moved the model:
#   ||p_a - p_b|| / ||p_b - p_init||  (global L2 over all leaves)
# and within AUC_TOL in final AUC-ROC.  A lost or doubled round moves
# the first ratio by about 1/loops.
PARAM_TOL = 5e-2
AUC_TOL = 1e-2
APOZ_SHAPES = ((2048, 256), (2048, 512), (2048, 64))
SEED = 0
LR = 0.05            # the launcher's default; SCBF uses LR / K
NOTE = "(one smoke run, not a benchmark)"


@dataclass(frozen=True)
class Size:
    admissions: int = 30760
    medicines: int = 2917
    hidden: tuple = (256, 64)
    clients: int = 5
    batch_size: int = 256
    local_epochs: int = 2
    loops: int = 3


class SmokeFailure(RuntimeError):
    pass


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def make_cohort(size: Size):
    from repro.data.medical import generate_cohort
    return generate_cohort(num_admissions=size.admissions,
                           num_medicines=size.medicines, seed=SEED)


def features(size: Size):
    return (size.medicines,) + tuple(size.hidden) + (1,)


def train_config(size: Size, method: str, *, fuse_rounds: int = 1,
                 pods: int = 1, prune: bool = False):
    from repro.config import (FedConfig, ObsConfig, ScbfConfig,
                              TrainConfig)
    lr = LR / size.clients if method == "scbf" else LR
    scbf = ScbfConfig(num_clients=size.clients, prune=prune,
                      prune_impl="mask" if prune else "reshape")
    return TrainConfig(learning_rate=lr, global_loops=size.loops,
                       local_epochs=size.local_epochs,
                       local_batch_size=size.batch_size, seed=SEED,
                       scbf=scbf,
                       fed=FedConfig(fuse_rounds=fuse_rounds, pods=pods),
                       obs=ObsConfig(device_metrics=True))


def initial_params(size: Size):
    """The model ``run_federated`` starts from (same seed split)."""
    import jax
    from repro.models.mlp_net import init_mlp
    _, init_key = jax.random.split(jax.random.PRNGKey(SEED))
    return init_mlp(features(size), init_key)


def _leaves(params):
    import jax
    import numpy as np
    return [np.asarray(x, np.float64)
            for x in jax.tree_util.tree_leaves(jax.device_get(params))]


def check_run(res, method: str) -> None:
    """The checks every run must pass."""
    import numpy as np
    require(all(np.isfinite(x).all() for x in _leaves(res.final_params)),
            f"{method}: non-finite final params")
    losses = [r.train_loss for r in res.records if r.train_loss is not None]
    require(losses, f"{method}: no train losses recorded")
    require(all(math.isfinite(v) for v in losses),
            f"{method}: non-finite train loss {losses}")
    require(res.final.auc_roc > 0.5,
            f"{method}: final AUC-ROC {res.final.auc_roc} <= 0.5")
    if method == "scbf":
        bad = [(r.loop, r.sparse_bytes, r.dense_bytes) for r in res.records
               if r.sparse_bytes > r.dense_bytes]
        require(not bad, f"{method}: sparse_bytes > dense_bytes at {bad}")


def divergence(a, b, size: Size) -> float:
    """||a - b|| / ||b - p_init|| over all leaves (see PARAM_TOL)."""
    la, lb, l0 = _leaves(a), _leaves(b), _leaves(initial_params(size))
    diff = math.sqrt(sum(float(((x - y) ** 2).sum())
                         for x, y in zip(la, lb)))
    moved = math.sqrt(sum(float(((y - z) ** 2).sum())
                          for y, z in zip(lb, l0)))
    require(moved > 0.0, "training did not move the model")
    return diff / moved


def compare(res, ref, size: Size, what: str) -> dict:
    div = divergence(res.final_params, ref.final_params, size)
    dauc = abs(res.final.auc_roc - ref.final.auc_roc)
    require(div <= PARAM_TOL,
            f"{what}: param divergence {div} > PARAM_TOL {PARAM_TOL}")
    require(dauc <= AUC_TOL, f"{what}: |dAUC| {dauc} > AUC_TOL {AUC_TOL}")
    return {"param_divergence": div, "auc_diff": dauc}


def timed_runs(cohort, size: Size, cfg, method: str):
    """Run twice: (first result, {first_call_s, steady_s}).

    The first call includes compilation; the second reuses the jit
    caches.  Both runs pass ``check_run``.
    """
    import jax
    from repro.core.scbf import run_federated
    out, times = None, []
    for _ in range(2):
        t0 = time.perf_counter()
        res = run_federated(cohort, cfg, method=method,
                            mlp_features=features(size))
        jax.block_until_ready(res.final_params)
        times.append(time.perf_counter() - t0)
        check_run(res, method)
        if out is None:
            out = res
    return out, {"first_call_s": times[0], "steady_s": times[1]}


def phase_scbf(cohort, size: Size, ctx: dict) -> dict:
    res, info = timed_runs(cohort, size, train_config(size, "scbf"), "scbf")
    ctx["scbf"] = res
    return dict(info, auc_roc=res.final.auc_roc)


def phase_fedavg(cohort, size: Size, ctx: dict) -> dict:
    res, info = timed_runs(cohort, size, train_config(size, "fedavg"),
                           "fedavg")
    return dict(info, auc_roc=res.final.auc_roc)


def phase_fused_scbf(cohort, size: Size, ctx: dict) -> dict:
    from repro.fed.engine import (fused_compile_count,
                                  reset_fused_compile_count)
    require("scbf" in ctx, "fused_scbf needs the scbf phase's result")
    reset_fused_compile_count()
    res, info = timed_runs(
        cohort, size, train_config(size, "scbf", fuse_rounds=size.loops),
        "scbf")
    compiles = fused_compile_count()
    require(compiles <= 2, f"fused_scbf: {compiles} fused compiles > 2")
    return dict(info, auc_roc=res.final.auc_roc, fused_compiles=compiles,
                **compare(res, ctx["scbf"], size, "fused_scbf vs scbf"))


def phase_fused_scbfwp(cohort, size: Size, ctx: dict) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.fed.engine import (fused_compile_count,
                                  reset_fused_compile_count)
    from repro.kernels.apoz import apoz_batch_fractions, default_interpret
    reset_fused_compile_count()
    res, info = timed_runs(
        cohort, size,
        train_config(size, "scbf", fuse_rounds=size.loops, prune=True),
        "scbf")
    compiles = fused_compile_count()
    require(compiles <= 2, f"fused_scbfwp: {compiles} fused compiles > 2")
    sizes = [sum(r.hidden_sizes) for r in res.records]
    steps = sum(1 for a, b in zip([sum(size.hidden)] + sizes, sizes)
                if b < a)
    require(steps >= 2, f"fused_scbfwp: {steps} pruning steps < 2 "
                        f"(hidden sizes per loop {sizes})")
    # the scorer the pruner calls, compiled for this backend: on a TPU
    # its zero counts must be a Mosaic kernel, not interpreted jnp
    params = initial_params(size)
    xb = jnp.zeros((2048, size.medicines), jnp.float32)
    masks = tuple(jnp.ones((h,), jnp.float32) for h in size.hidden)
    hlo = apoz_batch_fractions.lower(tuple(params), xb, masks) \
        .compile().as_text()
    kernel = "tpu_custom_call" in hlo
    require(kernel or default_interpret(),
            "fused_scbfwp: APoZ scorer has no compiled Pallas kernel")
    return dict(info, auc_roc=res.final.auc_roc, fused_compiles=compiles,
                prune_steps=steps, hidden_sizes=sizes,
                apoz_kernel_compiled=kernel,
                backend=jax.default_backend())


def phase_apoz_kernel(cohort, size: Size, ctx: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.apoz import apoz_counts_pallas, column_block
    out = {}
    for b, n in APOZ_SHAPES:
        key = jax.random.PRNGKey(b + n)
        a = jax.nn.relu(jax.random.normal(key, (b, n), jnp.float32))
        got = np.asarray(apoz_counts_pallas(a, bn=column_block(n)))
        want = np.asarray(jnp.sum(a == 0.0, axis=0, dtype=jnp.int32))
        require(np.array_equal(got, want),
                f"apoz_kernel {(b, n)}: counts differ from jnp in "
                f"{int((got != want).sum())} columns")
        out[f"{b}x{n}"] = "equal"
    return out


def phase_pods(cohort, size: Size, ctx: dict, pods: int) -> dict:
    import jax
    require(len(jax.devices()) >= pods,
            f"pods: need {pods} devices, have {len(jax.devices())}")
    runs, infos = {}, {}
    for p in (1, pods):
        cfg = train_config(size, "scbf", fuse_rounds=size.loops, pods=p)
        runs[p], infos[p] = timed_runs(cohort, size, cfg, "scbf")
    return dict(one_device=infos[1], sharded=infos[pods], pods=pods,
                auc_roc=runs[pods].final.auc_roc,
                **compare(runs[pods], runs[1], size,
                          f"pods={pods} vs one device"))


SINGLE_CHIP_PHASES = (("scbf", phase_scbf), ("fedavg", phase_fedavg),
                      ("fused_scbf", phase_fused_scbf),
                      ("fused_scbfwp", phase_fused_scbfwp),
                      ("apoz_kernel", phase_apoz_kernel))


def run_phases(phases, cohort, size: Size) -> bool:
    """Run every phase, print one line each; True iff all passed."""
    ctx, ok = {}, True
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            info = fn(cohort, size, ctx)
        except Exception as e:  # report every phase, then fail the run
            ok = False
            traceback.print_exc()
            print(f"phase {name}: FAILED {type(e).__name__}: {e}",
                  file=sys.stderr, flush=True)
            continue
        info["phase_s"] = time.perf_counter() - t0
        print(f"phase {name}: ok {NOTE} {json.dumps(info, default=str)}",
              flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--pods", type=int, default=1,
                    help="run only the pod-sharded fused SCBF phase over "
                         "this many chips (and its one-chip reference)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    warm = Path(cache).is_dir() and any(Path(cache).iterdir())
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {len(devices)} {dev.platform} device(s)",
              file=sys.stderr)
        return 2
    print(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache} ({'warm' if warm else 'cold'})",
          flush=True)

    size = Size()
    t0 = time.perf_counter()
    cohort = make_cohort(size)
    print(f"cohort {cohort.x_train.shape[0]}+{cohort.x_val.shape[0]}+"
          f"{cohort.x_test.shape[0]} x {cohort.num_features} built in "
          f"{time.perf_counter() - t0:.3f}s {NOTE}", flush=True)
    if args.pods > 1:
        phases = (("pods", lambda c, s, x: phase_pods(c, s, x, args.pods)),)
    else:
        phases = SINGLE_CHIP_PHASES
    if not run_phases(phases, cohort, size):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
