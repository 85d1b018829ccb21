"""Training launchers.

Two modes:

* ``medical`` — the paper's experiment: SCBF / SCBFwP / FedAvg / FAwP on
  the (synthetic) 30,760 × 2,917 medical cohort, 5 clients.  Writes a
  CSV history per method.

* ``lm`` — federated SCBF fine-tuning of a reduced assigned architecture
  on the synthetic token stream, exercising the exact
  ``make_federated_train_step`` used by the multi-pod dry-run (on CPU
  with a host mesh).

Usage:
    PYTHONPATH=src python -m repro.launch.train --mode medical \
        --methods scbf,fedavg,scbfwp --loops 30 --out experiments/medical
    PYTHONPATH=src python -m repro.launch.train --mode lm \
        --arch qwen2-0.5b --steps 200 --clients 4
"""
from __future__ import annotations

import argparse
import csv
import dataclasses
import functools
import os
import time

import numpy as np


@functools.lru_cache(maxsize=None)
def _fed_lm_step(bundle, scbf, lr: float):
    """One jitted federated step per (bundle, scbf cfg, lr).

    ``ScbfConfig`` is frozen (value-hashed) and ``ModelBundle`` hashes
    by identity, so repeated ``run_lm`` calls against the same bundle
    reuse the wrapper and its compilation cache instead of retracing
    (tracelint TL001).
    """
    import jax
    from repro.core.distributed import make_federated_train_step
    return jax.jit(make_federated_train_step(
        lambda p, b: bundle.loss_fn(p, b), scbf, lr=lr))


import contextlib


def run_medical(args):
    import jax
    from repro.config import FedConfig, ObsConfig, ScbfConfig, TrainConfig
    from repro.core.scbf import run_federated
    from repro.data.medical import generate_cohort
    from repro.obs import recording

    from repro.config import ClockConfig
    from repro.fed.faults import parse_fault_trace

    cohort = generate_cohort(seed=args.seed)
    os.makedirs(args.out, exist_ok=True)
    results = {}
    # --fault-trace / --deadline-quantile arm the chaos model
    # (docs/FED_ENGINE.md §Fault model & resilience): the fault trace
    # is seeded, so a chaos run replays bit-identically from its spec
    faults = parse_fault_trace(args.fault_trace) if getattr(
        args, "fault_trace", None) else None
    clock = None
    if getattr(args, "deadline_quantile", 0.0) > 0:
        clock = ClockConfig(enabled=True,
                            deadline_quantile=args.deadline_quantile,
                            deadline_action=getattr(args, "deadline_action",
                                                    "drop"))
    fed_kwargs = dict(
        engine=getattr(args, "engine", "batched"),
        sample_fraction=getattr(args, "sample_fraction", 1.0),
        dropout_rate=getattr(args, "dropout_rate", 0.0),
        straggler_rate=getattr(args, "straggler_rate", 0.0),
        partition=getattr(args, "partition", "iid"),
        dirichlet_alpha=getattr(args, "dirichlet_alpha", 0.5),
        min_valid_participants=getattr(args, "min_valid_participants", 0),
        max_update_norm=getattr(args, "max_update_norm", 0.0),
        norm_action=getattr(args, "norm_action", "reject"))
    if faults is not None:
        if "seed=" not in args.fault_trace:  # default the trace seed to --seed
            faults = dataclasses.replace(faults, seed=args.seed)
        fed_kwargs["faults"] = faults
    if clock is not None:
        fed_kwargs["clock"] = clock
    fed = FedConfig(**fed_kwargs)
    for method in args.methods.split(","):
        base = method.replace("wp", "")
        prune = method.endswith("wp")
        # SCBF sums K client deltas (paper Algorithm 1); FA averages.
        # Scale SCBF's local lr by 1/K for an equal effective server step.
        m_lr = args.lr / args.clients if base == "scbf" else args.lr
        cfg = TrainConfig(
            learning_rate=m_lr, global_loops=args.loops,
            local_epochs=args.local_epochs,
            local_batch_size=args.batch_size, seed=args.seed,
            scbf=ScbfConfig(upload_rate=args.upload_rate,
                            selection=args.selection,
                            num_clients=args.clients, prune=prune,
                            prune_rate=args.prune_rate,
                            prune_total=args.prune_total,
                            prune_impl=getattr(args, "prune_impl",
                                               "reshape"),
                            dp_noise_multiplier=getattr(
                                args, "dp_noise", 0.0)),
            fed=fed,
            # the event log's per-round train_loss comes from the device
            # metrics, which only this switch turns on
            obs=ObsConfig(device_metrics=getattr(args, "events", False)))
        # --events: one flight-recorder JSONL per method, feed it to
        # ``python -m repro.obs.report`` (docs/OBSERVABILITY.md)
        rec_ctx = recording(os.path.join(args.out, f"{method}.events.jsonl")) \
            if getattr(args, "events", False) else contextlib.nullcontext()
        with rec_ctx:
            res = run_federated(cohort, cfg, method=base, verbose=True)
        results[method] = res
        path = os.path.join(args.out, f"{res.method}.csv")
        with open(path, "w", newline="") as f:
            w = csv.writer(f)
            w.writerow(["loop", "auc_roc", "auc_pr", "upload_fraction",
                        "sparse_bytes", "dense_bytes", "wall_time",
                        "wall_is_amortized", "train_loss",
                        "flops_proxy", "hidden_sizes", "participants",
                        "epsilon"])
            for r in res.records:
                w.writerow([r.loop, r.auc_roc, r.auc_pr, r.upload_fraction,
                            r.sparse_bytes, r.dense_bytes, r.wall_time,
                            int(r.wall_is_amortized),
                            "" if r.train_loss is None else r.train_loss,
                            r.flops_proxy,
                            "x".join(map(str, r.hidden_sizes)),
                            r.num_participants,
                            "" if r.epsilon is None else r.epsilon])
        print(f"[{res.method}] best auc_roc={res.best('auc_roc'):.4f} "
              f"auc_pr={res.best('auc_pr'):.4f} "
              f"time={res.total_time():.1f}s upload={res.total_upload_bytes()/1e6:.1f}MB")
    return results


def run_lm(args):
    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.config import ScbfConfig
    from repro.data.tokens import SyntheticTokenStream
    from repro.models import model_zoo

    cfg = configs.smoke_variant(configs.get(args.arch))
    bundle = model_zoo.build(cfg)
    params, _ = bundle.init(jax.random.PRNGKey(args.seed))
    scbf = ScbfConfig(upload_rate=args.upload_rate, num_clients=args.clients)
    step = _fed_lm_step(bundle, scbf, args.lr)

    K, B, S = args.clients, args.batch_size, args.seq_len
    stream = SyntheticTokenStream(K * B, S, cfg.vocab_size, seed=args.seed)
    t0 = time.time()
    for i, nb in zip(range(args.steps), stream):
        batch = {k: jnp.asarray(v).reshape(K, B, S) for k, v in nb.items()}
        if cfg.frontend == "vision":
            batch["image_embeds"] = jnp.zeros(
                (K, B, cfg.num_patch_tokens, cfg.d_model), jnp.bfloat16)
        elif cfg.encoder_layers:
            batch["audio_embeds"] = jnp.zeros(
                (K, B, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
        loss, params = step(params, batch)
        if i % max(1, args.steps // 20) == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss {float(loss):.4f} "
                  f"({(time.time()-t0):.1f}s)", flush=True)
    return params


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["medical", "lm"], default="medical")
    ap.add_argument("--methods", default="scbf,fedavg,scbfwp,fedavgwp")
    ap.add_argument("--loops", type=int, default=30)
    ap.add_argument("--clients", type=int, default=5)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--local-epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--upload-rate", type=float, default=0.10)
    ap.add_argument("--selection", default="positive")
    ap.add_argument("--prune-rate", type=float, default=0.10)
    ap.add_argument("--prune-total", type=float, default=0.47)
    ap.add_argument("--prune-impl", default="reshape",
                    choices=["reshape", "mask"],
                    help="mask = static keep-masks (no recompiles, "
                         "fused-path compatible; scbf only)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="experiments/medical")
    # cross-device federation scenarios (docs/FED_ENGINE.md)
    ap.add_argument("--engine", default="batched",
                    choices=["batched", "sequential"])
    ap.add_argument("--sample-fraction", type=float, default=1.0)
    ap.add_argument("--dropout-rate", type=float, default=0.0)
    ap.add_argument("--straggler-rate", type=float, default=0.0)
    ap.add_argument("--partition", default="iid",
                    choices=["iid", "dirichlet"])
    ap.add_argument("--dirichlet-alpha", type=float, default=0.5)
    ap.add_argument("--dp-noise", type=float, default=0.0,
                    help="DP noise multiplier on scbf uploads (0 = off)")
    # chaos / resilience (docs/FED_ENGINE.md §Fault model & resilience)
    ap.add_argument("--fault-trace", default=None,
                    help="seeded fault-injection spec, comma-separated "
                         "key=value pairs (e.g. 'crash=0.05,net_fail=0.1,"
                         "bitflip=0.02,nan=0.01'); keys: seed, crash, "
                         "net_fail, retries, backoff, duplicate, bitflip, "
                         "nan, poison, poison_scale")
    ap.add_argument("--deadline-quantile", type=float, default=0.0,
                    help="enable the simulated wall clock and cut each "
                         "cohort at this latency quantile (0 = off)")
    ap.add_argument("--deadline-action", default="drop",
                    choices=["drop", "spill"],
                    help="what happens to deadline misses: drop, or spill "
                         "into a staleness-weighted buffer")
    ap.add_argument("--min-valid-participants", type=int, default=0,
                    help="round quorum: retry with backoff when fewer "
                         "valid updates arrive (0 = off)")
    ap.add_argument("--max-update-norm", type=float, default=0.0,
                    help="server-side L2 norm bound on admitted updates "
                         "(0 = off)")
    ap.add_argument("--norm-action", default="reject",
                    choices=["reject", "clip"],
                    help="over-norm updates are rejected or clipped")
    ap.add_argument("--events", action="store_true",
                    help="write <out>/<method>.events.jsonl flight-recorder "
                         "logs (repro.obs; view with python -m "
                         "repro.obs.report)")
    # lm mode
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=64)
    args = ap.parse_args()
    if args.mode == "medical":
        run_medical(args)
    else:
        if args.mode == "lm" and args.batch_size == 256:
            args.batch_size = 4
        run_lm(args)


if __name__ == "__main__":
    main()
