"""Benchmark harness — prints ONE JSON line for the driver.

Headline metric: single-chip GPT training throughput (tokens/sec) on the
flagship decoder-only model, bf16 compute.

Every section runs behind ``_section``, which prints a per-section JSON
line the moment the section finishes (so a later crash can't erase
earlier results).  A section that raises or returns nothing FAILS THE
RUN: no retry, no record with value 0, no exit code 0.  The training
headline needs a TPU and says so when there is none; the peak table has
no row for anything else, and a ``device_kind`` it does not list is an
error.  (ROADMAP S0 replaces this file's structure; until then it is
only kept unable to lie.)

``vs_baseline`` normalizes across hardware and model size via MFU (model
FLOPs utilization, train FLOPs ≈ 6·N·tokens): the reference's headline
training number is the GPT-J-6B DeepSpeed ZeRO-3 fine-tune at 4.565
samples/s × 512 tokens on 16× T4 (`release/air_examples/
gptj_deepspeed_finetuning/gptj_deepspeed_fine_tuning.ipynb`, BASELINE.md) →
146 tokens/s/GPU → 6·6.05e9·146 / 65e12 (T4 fp16 peak) ≈ 8.15% MFU.
``vs_baseline`` = our MFU / 0.0815, so >1.0 means better hardware
utilization than the reference's own headline run.
"""

from __future__ import annotations

import json
import time

REF_MFU = 0.0815  # reference GPT-J-6B fine-tune (see module docstring)

PEAK_FLOPS = {
    # per-chip dense bf16 peak
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v4": 275e12,
    "TPU v3": 123e12,
    "TPU v2": 46e12,
    "TPU v6 lite": 918e12,   # v6e
    "TPU v6e": 918e12,
    "TPU v7": 4614e12,       # ironwood
}


def _peak_for(device) -> float:
    """Peak bf16 FLOP/s of ``device``.  A ``device_kind`` the table does
    not list is an error, never a default: a utilization against a
    guessed peak is not a measurement."""
    kind = str(device.device_kind).lower()
    for name, peak in PEAK_FLOPS.items():
        if name.lower() in kind:
            return peak
    raise ValueError(
        f"no peak FLOP/s known for device_kind {device.device_kind!r}; add "
        "its row (with a source) to bench.PEAK_FLOPS"
    )


def _section(sections: dict, name: str, fn):
    """Run one bench section and emit its own JSON line the moment it
    finishes, so a later crash cannot erase it.  A section that raises
    fails the run with its own traceback; one that returns nothing
    (subprocess-wrapped sections whose child printed no record) fails
    it here."""
    result = fn()
    if not result:
        raise RuntimeError(f"bench section {name} produced no result")
    sections[name] = {"section": name, "ok": True}
    print(json.dumps(sections[name]), flush=True)
    return result


def main():
    sections: dict = {}
    # the host-only and CPU-pinned sections run (and exit) in subprocesses
    # before this process first touches jax: a chip belongs to one process
    core = _section(sections, "core_microbench", _core_microbench)
    core_obs = _section(sections, "core_obs_ab", _core_obs_ab)
    llm = _section(sections, "llm_serving", _llm_serving_bench)
    phases_ab = _section(sections, "llm_phases_ab", _llm_phases_ab)
    prefix = _section(sections, "llm_prefix", _llm_prefix_bench)
    fit = _section(sections, "gptj_fit_proof", _gptj_fit_proof)
    train = _section(sections, "train_headline", _train_headline)
    # _train_headline's state is freed with its frame — the 6B forward
    # gets the HBM back before this section allocates
    silicon = _section(sections, "gptj_6b_silicon", _gptj_6b_silicon)

    detail = dict(train["detail"])
    detail["core"] = core
    # recorder+series ON vs OFF on the task/object hot path
    detail["core_obs_ab"] = core_obs
    # continuous-batching serving engine vs sequential static-batch decode
    # under staggered arrivals + speculative-decode comparison
    # (ray_tpu/llm/bench.py) — a CPU-pinned subprocess, labelled as such
    detail["llm_serving"] = llm
    # per-request phase-ledger stamping ON vs OFF on the engine hot loops
    detail["llm_phases_ab"] = phases_ab
    # cross-request prefix cache on the shared-system-prompt workload
    detail["llm_prefix"] = prefix
    detail["gptj_6b_compiles"] = bool(fit.get("compiles"))
    detail["gptj_6b_fit"] = fit
    detail.update(silicon)
    detail["sections"] = sections
    print(
        json.dumps(
            {
                "metric": "gpt_train_tokens_per_sec_per_chip",
                "value": train["value"],
                "unit": "tokens/s",
                "vs_baseline": train["vs_baseline"],
                "detail": detail,
            }
        )
    )


def _train_headline() -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import build_train_step

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"the training headline measures a TPU; jax found {dev.platform!r} "
            f"({dev.device_kind}). There is no CPU stand-in for a device metric."
        )
    # 406M-param GPT, bf16, Pallas flash attention (1024x1024 blocks),
    # fused cross-entropy with ONE full-width pass (ce_chunks=1), remat
    # policy "attn" (keeps only flash out+lse), batch 26.  These choices
    # come from sweeps on a machine that is gone; on the current one they
    # are not measured (ROADMAP S4 re-measures, D3 then fixes the knobs).
    cfg = GPTConfig(
        vocab_size=50_304, seq_len=1024, d_model=1024, n_layers=24, n_heads=16,
        remat_policy="attn", ce_chunks=1,
    )
    batch = 26
    steps = 8

    mesh = make_mesh(MeshConfig(dp=1, fsdp=1, tp=1, sp=1), devices=[dev])

    def loss_fn(params, tokens):
        return gpt_loss(cfg, params, tokens, mesh)

    init_fn, step_fn = build_train_step(loss_fn, optax.adamw(1e-4), mesh)
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
    state = init_fn(params)
    del params

    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, cfg.seq_len + 1), 0, cfg.vocab_size, jnp.int32
    )
    tokens = jax.device_put(tokens, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()))

    # warmup / compile.  The barrier covers the UPDATED state, not just
    # the loss: the loss is computed before the optimizer writes, so a
    # loss-only barrier would exclude the final update's tail.
    state, loss = step_fn(state, tokens)
    jax.block_until_ready((state, loss))

    # MEDIAN of three windows — robust to one bad window without switching
    # the metric to best-case (the reference baseline is a sustained average)
    dts = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, loss = step_fn(state, tokens)
        jax.block_until_ready((state, loss))
        dts.append(time.perf_counter() - t0)
    dt = sorted(dts)[len(dts) // 2]

    tok_per_step = batch * cfg.seq_len
    tok_per_sec = steps * tok_per_step / dt
    mfu = 6.0 * n_params * tok_per_sec / _peak_for(dev)

    detail = {
        "model_params": n_params,
        "mfu": round(mfu, 4),
        "platform": dev.platform,
        "device": dev.device_kind,
        "device_count": len(jax.devices()),
        "loss": float(loss),
    }
    return {
        "value": round(tok_per_sec, 1),
        "vs_baseline": round(mfu / REF_MFU, 3),
        "detail": detail,
    }


def _cpu_child(argv: list, timeout: int, env_overrides=None) -> str:
    """Run one host-only bench in a child PINNED TO THE CPU and return its
    stdout.  These sections time the runtime's host paths (or, for the
    ``llm`` ones, a toy model on XLA's CPU backend); their records carry
    ``"platform": "cpu"`` so no number of theirs reads as a device metric.
    The child exits before this process first touches jax.  A child that
    fails, fails the run."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_overrides or {})
    out = subprocess.run(
        [sys.executable, *argv],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"{' '.join(argv)} exited {out.returncode}: {out.stderr[-500:]}"
        )
    return out.stdout


def _record(stdout: str, metric: str) -> dict:
    """The last JSON line of ``stdout`` whose ``metric`` matches."""
    for line in reversed(stdout.splitlines()):
        if line.startswith("{"):
            rec = json.loads(line)
            if rec.get("metric") == metric:
                return rec
    raise RuntimeError(f"child printed no {metric!r} record")


def _run_bench_core(metric: str, extra_args=(), env_overrides=None, timeout=600) -> dict:
    """Run ``bench_core.py`` and return the JSON record whose ``metric``
    matches — one scaffold for every core section."""
    stdout = _cpu_child(["bench_core.py", *extra_args], timeout, env_overrides)
    return _record(stdout, metric)


def _core_microbench() -> dict:
    """Runtime-core throughput next to the training metric (the
    reference's ray_perf metric names)."""
    rec = _run_bench_core("core_microbench")
    detail = rec.get("detail", {})
    if rec.get("env"):
        # Contention context (cpu count, loadavg, spin canary) so
        # cross-round comparisons of the core numbers are interpretable
        detail["_env"] = rec["env"]
    return detail


def _core_obs_ab() -> dict:
    """Observability-overhead A/B on the core task/object hot path: run
    ``bench_core.py --obs-ab`` twice in subprocesses — flight recorder +
    metric time-series ON, then OFF (both knobs are import-time, so a
    fresh process per arm is the only honest A/B) — and report both
    numbers plus the ON/OFF ratio per microbench.  A ratio well below
    1.0 says the recorder/series machinery owns that share of the cost;
    a ratio ≈ 1.0 acquits it."""

    def one_arm(obs_on: bool) -> dict:
        flag = "1" if obs_on else "0"
        rec = _run_bench_core(
            "core_obs_ab", extra_args=("--obs-ab",),
            env_overrides={"RAY_TPU_EVENTS": flag,
                           "RAY_TPU_METRICS_SERIES": flag},
            timeout=300,
        )
        return rec.get("detail", {})

    on = one_arm(True)
    off = one_arm(False)
    ratios = {
        k: round(on[k] / off[k], 4)
        for k in on
        if k in off and off[k] > 0
    }
    return {"obs_on": on, "obs_off": off, "on_over_off_ratio": ratios}


def _llm_serving_bench() -> dict:
    """Continuous-batching vs static-batch decode throughput under
    staggered arrivals, plus the speculative-decode comparison
    (``python -m ray_tpu.llm.bench`` prints one record per benchmark) —
    the toy model on the CPU backend (``_cpu_child``)."""
    # just the serving benches — the prefix workload has its own
    # section (_llm_prefix_bench) and must not run twice
    stdout = _cpu_child(["-m", "ray_tpu.llm.bench", "--only", "serving"], 600)
    cont = _record(stdout, "llm_continuous_batching_tokens_per_sec")
    spec = _record(stdout, "llm_speculative_decode_speedup")
    return {
        "platform": "cpu",
        "continuous_tokens_per_sec": cont["value"],
        "speedup_vs_static": cont["vs_baseline"],
        **cont.get("detail", {}),
        "speculative": {
            "spec_tokens_per_sec": spec["value"],
            "speedup_vs_nonspec": spec["vs_baseline"],
            **spec.get("detail", {}),
        },
    }


def _llm_phases_ab() -> dict:
    """Phase-ledger stamping ON vs OFF on the continuous-batching engine
    (``python -m ray_tpu.llm.bench --only continuous``), same honest-A/B
    shape as ``_core_obs_ab``: ``RAY_TPU_PHASES`` is import-time, so each
    arm is a fresh CPU-pinned subprocess.  The per-request ledger rides
    the engine's admission/prefill/decode hot loops — a ratio ≈ 1.0 says
    the stamping (a list add + two float ops per transition, zero locks)
    stays within noise; the acceptance bar is OFF/ON ≤ 1.05."""

    def one_arm(phases_on: bool) -> float:
        stdout = _cpu_child(
            ["-m", "ray_tpu.llm.bench", "--only", "continuous"], 600,
            {"RAY_TPU_PHASES": "1" if phases_on else "0"},
        )
        rec = _record(stdout, "llm_continuous_batching_tokens_per_sec")
        return float(rec["value"])

    on = one_arm(True)
    off = one_arm(False)
    return {
        "platform": "cpu",
        "phases_on_tokens_per_sec": on,
        "phases_off_tokens_per_sec": off,
        "on_over_off_ratio": round(on / off, 4) if off else None,
    }


def _llm_prefix_bench() -> dict:
    """Cross-request prefix cache on the shared-system-prompt workload
    (``python -m ray_tpu.llm.bench --only prefix``): N requests with a
    common 256-token prefix, cache on vs off — prefill tokens computed,
    warm-request TTFT, token-identity asserted in the subprocess."""
    stdout = _cpu_child(["-m", "ray_tpu.llm.bench", "--only", "prefix"], 600)
    rec = _record(stdout, "llm_prefix_cache_warm_ttft_speedup")
    return {
        "platform": "cpu",
        "warm_ttft_speedup": rec["value"],
        **rec.get("detail", {}),
    }


def _gptj_6b_silicon() -> dict:
    """GPT-J-6B on the chip: a full bf16 forward at seq 2048 and a short
    KV-cache greedy decode, with the true GPT-J architecture
    (models/gptj.py — the HF-checkpoint-import target whose conversion is
    logit-exact, test_train_integrations.py::TestGPTJ).  Weights are
    seeded-random AT THE 6B SHAPE, generated directly on device in bf16
    (12.1 GB; the arithmetic is weight-value-independent)."""
    import gc

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gptj import (
        GPTJConfig,
        gptj_decode,
        gptj_forward,
        gptj_init,
    )

    gc.collect()
    cfg = GPTJConfig(
        vocab_size=50_432,  # HF 50400 padded to the MXU lane multiple
        remat=False,  # inference: no backward to rematerialize for
        dtype="bfloat16",
    )

    def init_bf16():
        p = gptj_init(jax.random.PRNGKey(7), cfg)
        return jax.tree.map(lambda x: x.astype(jnp.bfloat16), p)

    params = jax.jit(init_bf16)()  # generated on-device: no 24 GB host tree
    jax.block_until_ready(params)
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))

    fwd = jax.jit(lambda p, t: gptj_forward(cfg, p, t))
    tokens = jnp.asarray(
        jax.random.randint(jax.random.PRNGKey(8), (1, 2048), 0, 50_400),
        jnp.int32,
    )
    fwd(params, tokens).block_until_ready()  # compile
    dts = []
    for _ in range(3):
        t0 = time.perf_counter()
        fwd(params, tokens).block_until_ready()
        dts.append(time.perf_counter() - t0)
    fwd_tok_s = 2048 / sorted(dts)[1]

    n_new = 16
    dec = jax.jit(lambda p, t: gptj_decode(cfg, p, t, n_new))
    prompt = tokens[:, :128]
    dec(params, prompt).block_until_ready()
    t0 = time.perf_counter()
    dec(params, prompt).block_until_ready()
    dec_tok_s = n_new / (time.perf_counter() - t0)
    return {
        "gptj_6b_params": n_params,
        "gptj_6b_forward_tokens_per_sec": round(fwd_tok_s, 1),
        "gptj_6b_decode_tokens_per_sec": round(dec_tok_s, 1),
    }


def _gptj_fit_proof() -> dict:
    """GPT-J-6B fsdp-8 AOT fit proof on a virtual CPU mesh (subprocess: it
    must not share this process's TPU backend). See
    ray_tpu/parallel/fit_proof.py."""
    import os

    stdout = _cpu_child(
        ["-m", "ray_tpu.parallel.fit_proof"], 900,
        {
            "XLA_FLAGS": (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8"
            ).strip()
        },
    )
    for line in reversed(stdout.splitlines()):
        if line == "{" or line.startswith('{"'):
            return json.loads(line)
    raise RuntimeError("fit proof printed no report")


if __name__ == "__main__":
    main()
