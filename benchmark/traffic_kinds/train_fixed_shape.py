"""Traffic kind ``train_fixed_shape``: a pre-training job at one fixed
batch shape, through ``JaxTrainer(ScalingConfig(num_workers=1))`` →
``build_train_step``.  The loop below is the benchmark's own
``train_loop_per_worker``; the step, the model, the loss and the
optimizer wiring are the program's, found through the configuration's
FAMILY (``families/<family>.py``), which also names the plain reference.

A new batch arrives every step from a host loader thread that runs one
step ahead (Zipf-distributed token ids from ``--seed``), so a wait for
data is possible and is measured, and the loss has something to learn:
the unigram distribution.

The window is bracketed by barriers on the UPDATED state.  Inside it the
loop never waits for the step it just dispatched, only for the one
``run_ahead`` steps back, so the device always has the next step queued.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import queue
import threading
import time


def batches(seed: int, batch: int, seq_len: int, vocab: int, zipf_s: float):
    """An endless iterator of (batch, seq_len + 1) int32 token ids with a
    Zipf(``zipf_s``) unigram distribution; the same seed gives the same
    batches."""
    import numpy as np

    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, vocab + 1) ** zipf_s
    cdf = np.cumsum(p / p.sum())
    while True:
        u = rng.random((batch, seq_len + 1))
        yield np.minimum(np.searchsorted(cdf, u), vocab - 1).astype(np.int32)


class _Loader:
    """Host loader: a thread that keeps ``depth`` batches ready."""

    def __init__(self, it, depth: int = 1):
        self.q: queue.Queue = queue.Queue(maxsize=depth)
        self.stop = threading.Event()
        self.t = threading.Thread(target=self._fill, args=(it,), daemon=True)
        self.t.start()

    def _fill(self, it):
        for b in it:
            while not self.stop.is_set():
                try:
                    self.q.put(b, timeout=0.1)
                    break
                except queue.Full:
                    pass
            if self.stop.is_set():
                return

    def next(self):
        return self.q.get()

    def close(self):
        self.stop.set()
        self.t.join()


def _reference_loss(cache_dir: str, key_obj, compute) -> tuple:
    """The reference's loss on the probe, cached by configuration: weights
    and probe are fixed by the configuration, so only the first run in a
    checkout computes it."""
    key = hashlib.sha256(json.dumps(key_obj, sort_keys=True).encode()).hexdigest()[:24]
    path = os.path.join(cache_dir, "verdicts", f"train-ref-loss-{key}.json")
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)["loss"], True
    value = float(compute())
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump({"loss": value}, f)
    return value, False


def train_loop(config: dict) -> None:
    """``train_loop_per_worker``: the one process on the chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmark import harness as H
    from ray_tpu import train
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import build_train_step
    from ray_tpu.util.device_prof import device_report

    marks = {"loop_start": time.time()}
    sizes, traffic = config["sizes"], config["traffic"]
    cfg = H.family_piece(sizes, "model_config")(sizes)
    init = H.family_piece(sizes, "program_init")()
    model_loss = H.family_piece(sizes, "loss")
    reference_loss = H.family_piece(sizes, "reference_loss")
    batch = sizes["train"]["batch"]
    mesh = make_mesh(MeshConfig(dp=-1, fsdp=1, tp=1, sp=1), devices=jax.devices())
    traces = []

    def loss_fn(params, tokens):
        traces.append(None)  # runs only while jax traces
        return model_loss(cfg, params, tokens, mesh)

    init_fn, step_fn = build_train_step(
        loss_fn, optax.adamw(sizes["train"]["learning_rate"]), mesh
    )
    params = init(jax.random.PRNGKey(sizes["train"]["weights_seed"]), cfg)
    marks["params"] = time.time()

    # -- correctness (c): the system's loss against the plain reference, on
    # a probe fixed by the configuration, with the initial weights
    probe = next(batches(
        sizes["correctness"]["probe_seed"], sizes["correctness"]["probe_sequences"],
        cfg.seq_len, cfg.vocab_size, traffic["zipf_s"],
    ))
    system_loss = float(jax.jit(lambda p, t: model_loss(cfg, p, t, mesh))(params, probe))
    ref_loss, ref_cached = _reference_loss(
        config["cache_dir"], [sizes, traffic["zipf_s"]],
        lambda: reference_loss(params, probe, cfg),
    )
    marks["reference"] = time.time()

    state = init_fn(params)
    del params
    jax.block_until_ready(state)
    marks["state"] = time.time()

    loader = _Loader(batches(config["seed"], batch, cfg.seq_len, cfg.vocab_size,
                             traffic["zipf_s"]))
    losses = []
    for _ in range(traffic["warmup_steps"]):
        state, loss = step_fn(state, loader.next())
        losses.append(loss)
    jax.block_until_ready((state, losses))
    marks["warm"] = time.time()
    traced_after_warmup = len(traces)

    seconds, run_ahead = config["seconds"], traffic["run_ahead"]
    trace_s = min(float(traffic.get("trace_s", 3.0)), seconds * 0.25)
    trace_dir, trace_on = config["trace_dir"], False
    wait_s = 0.0
    n0 = len(losses)
    t_open = time.time()
    while True:
        now = time.time()
        if now - t_open >= seconds:
            break
        if trace_dir and not trace_on and now - t_open >= seconds - trace_s:
            # the last seconds of the window; stopped after the final barrier
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # TraceAnnotations still land
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            trace_on = True
        t = time.time()
        with jax.profiler.TraceAnnotation("bench:next_batch"):
            tokens = loader.next()
        t1 = time.time()
        with jax.profiler.TraceAnnotation("bench:dispatch_step"):
            state, loss = step_fn(state, tokens)
        losses.append(loss)
        with jax.profiler.TraceAnnotation("bench:wait_step_behind"):
            if len(losses) - n0 > run_ahead:
                losses[-1 - run_ahead].block_until_ready()
        wait_s += t1 - t
    jax.block_until_ready((state, losses))  # the UPDATED state
    t_close = time.time()
    if trace_on:
        jax.profiler.stop_trace()
    loader.close()
    steps = len(losses) - n0
    train.report({
        "losses": [float(x) for x in np.asarray(jnp.stack(losses))],
        "warmup_steps": n0, "steps": steps, "t_open": t_open, "t_close": t_close,
        "tokens_per_step": batch * cfg.seq_len,
        "data_wait_s": wait_s,
        "system_loss": system_loss, "reference_loss": ref_loss,
        "reference_cached": ref_cached,
        "retraces": len(traces) - traced_after_warmup,
        "marks": marks, "device_report": device_report(),
        "n_params": sum(p.size for p in jax.tree_util.tree_leaves(state.params)),
        "model": dataclasses.asdict(cfg),
    })


def run(ctx: dict) -> dict:
    import math

    import ray_tpu
    from benchmark import harness as H
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    config, traffic, args = ctx["config"], ctx["traffic"], ctx["args"]
    sizes = {k: v for k, v in H.sizes(config, args.rehearsal).items() if k != "rehearsal"}
    trace_dir = os.path.join(ctx["run_dir"], "trace") if args.trace else None
    ray_tpu.init()
    try:
        t_init = time.time()
        result = JaxTrainer(
            train_loop,
            train_loop_config={
                "sizes": sizes,
                "traffic": traffic, "seed": args.seed, "seconds": float(args.seconds),
                "trace_dir": trace_dir, "cache_dir": H.CACHE_DIR,
            },
            scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(
                name="benchmark_train", storage_path=os.path.join(ctx["run_dir"], "train"),
            ),
        ).fit()
        t_fit = time.time()
    finally:
        ray_tpu.shutdown()
        killed = H.reap_descendants()
        if killed:
            H.note(f"killed leftover processes {killed}")
        t_down = time.time()
    if result.error is not None:
        raise result.error

    import jax._src.xla_bridge as xb

    H.check(not xb._backends, f"the driver opened backends {list(xb._backends)}")
    m = result.metrics
    rep = m["device_report"]
    device = {"platform": rep["platform"], "kind": rep["device_kind"],
              "count": rep["device_count"]}
    if not args.rehearsal:
        H.check(device["platform"] == "tpu",
                f"the train worker computed on {device['platform']}, not a TPU")
    losses, n0 = m["losses"], m["warmup_steps"]
    tol = sizes["correctness"]["loss_tolerance"]
    window_losses = losses[n0:]
    nonfinite = sum(1 for x in window_losses if not math.isfinite(x))
    learned = sum(losses[-5:]) / 5 < losses[0]
    ref_gap = abs(m["system_loss"] - m["reference_loss"])
    correctness = {
        "nonfinite_losses": nonfinite, "first_loss": losses[0],
        "last5_mean_loss": sum(losses[-5:]) / 5, "learned": learned,
        "system_loss": m["system_loss"], "reference_loss": m["reference_loss"],
        "reference_gap": ref_gap, "loss_tolerance": tol,
        "reference_cached": m["reference_cached"], "retraces": m["retraces"],
    }
    H.emit("correctness", **correctness)
    marks = m["marks"]
    # the worker's clock is this host's: its marks are this run's parts
    for part, t in (
        ("ray_init", t_init), ("worker_start", marks["loop_start"]),
        ("params_init", marks["params"]), ("reference_probe", marks["reference"]),
        ("state_init", marks["state"]), ("warmup_steps", marks["warm"]),
        ("window_wait", m["t_open"]), ("window", m["t_close"]),
        ("stop_trace_and_report", t_fit), ("shutdown", t_down),
    ):
        ctx["budget"].mark(part, t)
    H.emit(
        "setup_breakdown",
        ray_init_s=t_init - ctx["t_start"],
        worker_start_s=marks["loop_start"] - t_init,
        params_init_s=marks["params"] - marks["loop_start"],
        reference_probe_s=marks["reference"] - marks["params"],
        state_init_s=marks["state"] - marks["reference"],
        warmup_steps_s=marks["warm"] - marks["state"],
        compile_cache=rep["compile_cache"], versions=rep["versions"],
        n_params=m["n_params"],
    )
    peak = max(
        (s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
         for s in rep["memory"].values()), default=0,
    )
    return {
        "kind": "train",
        "correct": bool(
            nonfinite == 0 and learned and ref_gap <= tol and m["retraces"] == 0
            and all(math.isfinite(x) for x in losses)
        ),
        "attempted": m["steps"],
        "failed": nonfinite,
        "device": dict(device, memory_peak_bytes=int(peak)),
        "setup_s": m["t_open"] - ctx["t_start"],
        "seconds": m["t_close"] - m["t_open"],
        "train": m,
        "reference": {"cached": m["reference_cached"],
                      "seconds": marks["reference"] - marks["params"]},
        "trace_dir": trace_dir if args.trace else None,
        "device_report": rep,
        "model": m["model"],
        "batch": sizes["train"]["batch"],
        "config": config,
        "traffic": traffic,
    }
