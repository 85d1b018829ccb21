"""One run of one cell: set-up, the measured window, and the check.

The window drives ``repro.core.scbf.run_federated`` on its fused path,
unchanged: each call is one whole federated training job from a fresh
initialisation (partition, engine build, initial evaluation, rounds in
fused chunks, evaluation at each chunk boundary).  Every job of a run
uses the run's seed for data and weights, so the window repeats one
trajectory and meets no new shapes.

Set-up is the process start, the cohort generated on the host from the
seed, and one warm-up job identical to a window job, which compiles (or
loads from the persistent cache) every program the window runs.

With ``trace=1`` the warm-up and one traced job run under the program's
flight recorder (``repro.obs.trace.recording``, which also turns on its
device metrics, so the traced job runs that variant of the fused
program) and the traced job under ``jax.profiler``; the per-layer
metrics are read from that job.

The check compares what the last job produced with the plain reference
(``check.py``); it runs after the window has closed and the memory peak
has been read.
"""
from __future__ import annotations

import gc
import shutil
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from bench import check, cohort, reference, spec, tracereduce

TRACE_DIR = spec.BENCH / "_out" / "trace"


class ChipMissing(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


class Capture:
    """Every payload the current job encodes, in order, read where the
    program encodes it: ``repro.comm.wire.encode``, the documented
    privacy boundary that every upload passes (docs/WIRE_FORMAT.md).
    The payloads are kept as the encoder returns them; nothing the
    program computes changes."""

    def __init__(self):
        self.payloads: List[object] = []

    def reset(self):
        self.payloads = []

    def install(self):
        from repro.comm import wire
        encode = wire.encode

        def spy(*args, **kwargs):
            payload = encode(*args, **kwargs)
            self.payloads.append(payload)
            return payload

        wire.encode = spy
        self._restore = lambda: setattr(wire, "encode", encode)

    def uninstall(self):
        self._restore()


class CompileCounter:
    """Counts programs lowered or compiled while ``on``."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring
        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, name, *_, **__):
        if self.on and name in self.EVENTS:
            self.count += 1


def device_check(chips: int):
    """The devices the cell runs on; raises ``ChipMissing`` unless JAX
    finds at least ``chips`` TPUs of a kind in the peaks table."""
    import jax
    devices = jax.devices()
    kind = devices[0].device_kind
    if devices[0].platform != "tpu":
        raise ChipMissing(f"no TPU: JAX found {len(devices)} "
                          f"{devices[0].platform} device(s)")
    if len(devices) < chips:
        raise ChipMissing(f"the cell needs {chips} chips, JAX found "
                          f"{len(devices)}")
    if kind not in spec.peaks():
        raise ChipMissing(f"device kind {kind!r} is not in peaks.json")
    return devices


def train_config(cell: spec.Cell, seed: int):
    from repro.config import FedConfig, ScbfConfig, TrainConfig
    c, t = cell.config, cell.traffic
    prune = t.get("prune")
    scbf = ScbfConfig(
        upload_rate=c["upload_rate"], selection=c["selection"],
        num_clients=c["clients"], prune=prune is not None,
        prune_impl="mask" if prune else "reshape",
        prune_rate=prune["rate"] if prune else 0.1,
        prune_total=prune["total"] if prune else 0.47,
        prune_compact=prune["compact"] if prune else True)
    fed = FedConfig(fuse_rounds=t["fuse_rounds"], pods=cell.chips,
                    sample_fraction=c["sample_fraction"], partition="iid")
    return TrainConfig(learning_rate=c["learning_rate"],
                       global_loops=t["loops_per_job"],
                       eval_every=t["eval_every"],
                       local_epochs=c["local_epochs"],
                       local_batch_size=c["local_batch_size"],
                       seed=seed, scbf=scbf, fed=fed)


def make_cohort(cell: spec.Cell, seed: int):
    from repro.data.medical import MedicalCohort
    return MedicalCohort(*cohort.generate(**cell.config["cohort"],
                                          seed=seed))


class Job:
    """One federated training job of a cell, as the window runs it."""

    def __init__(self, cell: spec.Cell, seed: int, med=None):
        self.cell = cell
        self.cohort = med if med is not None else make_cohort(cell, seed)
        self.cfg = train_config(cell, seed)
        self.features = tuple(cell.config["features"])
        self.capture = Capture()

    def __call__(self):
        import jax
        from repro.core.scbf import run_federated
        self.capture.reset()
        res = run_federated(self.cohort, self.cfg,
                            method=self.cell.traffic["method"],
                            mlp_features=self.features)
        jax.block_until_ready(res.final_params)
        return res


def reference_side(cell: spec.Cell, job: Job, seed: int, final_params,
                   **kw) -> check.Side:
    """The first rounds of the job as ``reference.job_rounds`` runs them,
    and the evaluation of the seed's initial model and of the program's
    ``final_params`` on the whole test split (``kw``: the arithmetic,
    ``dtype`` and ``precision``, or a planted ``fault``)."""
    c = cell.config
    out = reference.job_rounds(
        job.cohort.x_train, job.cohort.y_train, features=job.features,
        num_clients=c["clients"], fraction=c["sample_fraction"],
        lr=c["learning_rate"], batch=c["local_batch_size"],
        epochs=c["local_epochs"], upload_rate=c["upload_rate"],
        selection=c["selection"], seed=seed,
        rounds=int(cell.limits["rounds"]), prune=cell.traffic.get("prune"),
        x_val=job.cohort.x_val, **kw)
    auc = evaluation(job, seed, final_params,
                     **{k: v for k, v in kw.items() if k != "fault"})
    return check.Side(out["uploads"], out["bytes"], auc)


def evaluation(job: Job, seed: int, final_params, fault=None,
               **kw) -> dict:
    """(AUC-ROC, AUC-PR) of the seed's initial model and of
    ``final_params`` by ``reference.evaluate`` (``kw``: its arithmetic).
    ``fault`` plants an evaluation fault: ``"eval_half"`` scores the
    first half of the test rows only, ``"eval_stale"`` scores the
    initial model in place of the final one."""
    init = reference.init_params(job.features, seed)
    final = [(layer["w"], layer["b"]) for layer in final_params]
    x, y = job.cohort.x_test, job.cohort.y_test
    if fault == "eval_half":
        x, y = x[:x.shape[0] // 2], y[:y.shape[0] // 2]
    if fault == "eval_stale":
        final = init
    return {"init": reference.evaluate(init, x, y, **kw),
            "final": reference.evaluate(final, x, y, **kw)}


def readings(cell: spec.Cell, seed: int, job: Job, res) -> Dict[str, float]:
    """The compared numbers of the job's last run against the reference."""
    init = reference.init_params(job.features, seed)
    rounds = int(cell.limits["rounds"])
    prog = check.program_side(res, job.capture.payloads, init, rounds)
    return check.numbers(prog, reference_side(cell, job, seed,
                                              res.final_params), rounds)


def _memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def _device_info(devices, chips: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices),
            "memory_peak_bytes": _memory_peak(devices[:chips])}


def _trace_context(cell: spec.Cell, trace: dict, rec, res, chips: int,
                   peak: dict) -> dict:
    """What the per-layer readers read from one traced job."""
    window = tracereduce.host_events(trace, "bench_job")[-1]
    planes = tracereduce.device_planes(trace)[:chips]
    busy = [tracereduce.busy(p, window) for p in planes]
    spans = [(e["name"], (e["ts"] - e["dur"]) * 1e9, e["ts"] * 1e9)
             for e in rec.events if e.get("ev") == "span"]
    chunk_rec = [s for n, s, _ in spans if n == "fused_chunk"]
    chunk_prof = [s for s, _ in tracereduce.host_events(trace,
                                                        "fused_chunk")
                  if window[0] <= s <= window[1]]
    off = tracereduce.align(chunk_rec, chunk_prof)
    aligned = [] if off is None else [(n, s + off, e + off)
                                      for n, s, e in spans]
    return {
        "cell": cell, "chips": chips, "peak": peak, "trace": trace,
        "window": window, "window_s": (window[1] - window[0]) / 1e9,
        "planes": planes,
        "busy_s": float(np.mean([tracereduce.total(b) for b in busy]))
        / 1e9 if busy else 0.0,
        "gaps": tracereduce.gaps(busy[0], window) if busy else [],
        "spans": aligned,
        "span_s": {n: sum(e - s for m, s, e in spans if m == n) / 1e9
                   for n in {m for m, _, _ in spans}},
        "records": res.records, "rounds": len(res.records),
    }


def _profile_options():
    """Device and host tracing, without the Python function tracer (the
    program's spans come from its own recorder)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        t_start: float, require_chip: bool = True,
        cell: Optional[spec.Cell] = None, log=sys.stderr) -> dict:
    """One run; returns the result object the command prints."""
    import jax
    bm = spec.benchmark()
    cell = cell or spec.cell(cell_name, bm)
    if require_chip:
        devices = device_check(cell.chips)
        peak = spec.peaks()[devices[0].device_kind]
    else:
        devices = jax.devices()
        peak = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
    from repro.obs import trace as obstrace

    counter = CompileCounter()
    t_devices = time.perf_counter()
    job = Job(cell, seed)
    t_cohort = time.perf_counter()
    job.capture.install()
    try:
        if trace:
            rec = obstrace.Recorder()
            with obstrace.recording(recorder=obstrace.Recorder()):
                job()
            setup_s = time.perf_counter() - t_start
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            counter.on = True
            with obstrace.recording(recorder=rec):
                with jax.profiler.trace(str(TRACE_DIR),
                                        profiler_options=_profile_options()):
                    with jax.profiler.TraceAnnotation("bench_job"):
                        res = job()
            jobs, rounds, window_s = 1, len(res.records), None
        else:
            job()
            setup_s = time.perf_counter() - t_start
            counter.on = True
            jobs, rounds, t0 = 0, 0, time.perf_counter()
            ends = [t0]
            while jobs == 0 or ends[-1] - t0 < seconds:
                res = job()
                ends.append(time.perf_counter())
                jobs += 1
                rounds += len(res.records)
            window_s = ends[-1] - t0
        counter.on = False
    finally:
        job.capture.uninstall()
    print(f"setup: {t_devices - t_start:.3f} s to the devices, "
          f"{t_cohort - t_devices:.3f} s cohort, "
          f"{t_start + setup_s - t_cohort:.3f} s warm-up job", file=log)
    print(f"window: {jobs} jobs, {rounds} rounds, "
          f"{counter.count} compilations inside", file=log)
    if not trace:
        print("job seconds: " + " ".join(
            f"{b - a:.3f}" for a, b in zip(ends, ends[1:])), file=log)
    log.flush()
    device = _device_info(devices, cell.chips)
    gc.collect()

    out: Dict[str, object] = {}
    breakdown = None
    if trace:
        tr = tracereduce.load_dir(str(TRACE_DIR))
        ctx = _trace_context(cell, tr, rec, res, cell.chips, peak)
        metrics = {}
        for m in spec.per_layer(cell.name, bm):
            value = spec.reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = ctx["busy_s"]
        device["window_s"] = ctx["window_s"]
        plane = ctx["planes"][0] if ctx["planes"] else None
        breakdown = {
            "device_ops": [[n, s] for n, s in tracereduce.top_ops(
                plane, ctx["window"])] if plane else [],
            "idle_gaps": [[n, s] for n, s in tracereduce.name_gaps(
                ctx["gaps"], ctx["spans"])],
        }
    else:
        ctx = {"cell": cell, "setup_s": setup_s, "window_s": window_s,
               "rounds": rounds, "jobs": jobs}
        metrics = {}
        for m in spec.end_to_end(cell.name, bm):
            value = spec.reader(m["name"]).read(ctx)
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    values = readings(cell, seed, job, res)
    limits = {k: float(v) for k, v in cell.limits["limits"].items()}
    # numbers without a limit (no readings to set one from yet) are
    # printed beside the compared ones, and decide nothing
    shown = {**{k: None for k in values}, **limits}
    for name, lim in shown.items():
        print(f"check {name}: {values.get(name)} limit {lim}", file=log)
    log.flush()
    out["correct"] = check.verdict(values, limits)
    out["attempted"] = jobs
    out["failed"] = 0
    out["metrics"] = metrics
    out["device"] = device
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {name: {"value": values.get(name), "limit": lim}
                     for name, lim in shown.items()}
    return out
