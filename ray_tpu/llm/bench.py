"""LLM engine benchmarks: continuous batching + speculative decoding.

``python -m ray_tpu.llm.bench`` prints TWO JSON lines:

* ``llm_continuous_batching_tokens_per_sec`` — aggregate decode tokens/s
  of the continuous-batching engine against the same workload run as
  sequential static-batch ``gptj_decode`` calls (the pre-``ray_tpu.llm``
  serving story), under staggered arrivals so the engine's advantage —
  new requests join the running batch mid-flight — is what gets measured.
* ``llm_speculative_decode_speedup`` — the spec_k=3 n-gram-drafted engine
  against the non-speculative engine on two workloads: a REPETITIVE one
  (patterned prompts whose greedy continuations go periodic early — the
  prompt-lookup drafter's home turf) and an ADVERSARIAL one (random
  prompts, short outputs: acceptance near zero, so what's measured is the
  backoff bound on regression).  Both paths must produce byte-identical
  greedy tokens — asserted, or the comparison is comparing different
  work.
* ``llm_prefix_cache_warm_ttft_speedup`` — the shared-system-prompt
  workload (N requests with a common 256-token prefix, distinct
  suffixes) through the prefix cache vs the same engine with the cache
  off: prefill-tokens-computed and warm-request TTFT are the headline
  numbers (the production chat regime the cache targets); outputs must
  be token-identical across the two arms — asserted.
* ``llm_multichip_tp_tokens_per_sec`` (``--only multichip``) — the
  tensor-parallel engine (``llm.multichip``, ``EngineConfig(tp=N)``)
  against the single-chip engine on the same workload: tokens/s, mean
  TTFT and per-device KV-pool bytes per mesh size, token identity
  asserted between every arm.  On the CPU host-device substrate the
  ratio measures shard_map/psum OVERHEAD (there is no real parallel
  hardware underneath — expect < 1x); on real TPUs the same pairing
  measures the multi-chip speedup.  The ``MULTICHIP_r0x`` CI artifact
  records these numbers.
* ``llm_loadgen_healthy_p99_s`` (``--only loadgen``) — the open-loop
  load harness (``llm.loadgen``): boots a served app and drives the
  three standard arms (healthy / overload / replica-kill), reporting the
  healthy-arm client p99 with the full per-phase attribution report in
  ``detail``.  Excluded from ``--only all`` — it boots a serve cluster
  and belongs to its own CI job (``loadgen-smoke``).

Sized to run on CPU in seconds (the same comparison holds on TPU with
the real model; the ratio is what travels).  ``--smoke`` shrinks the
workloads for CI.  Invoked by the top-level ``bench.py`` as a subprocess
so a failure never costs the headline metric.
"""

from __future__ import annotations

import json
import time

N_REQUESTS = 8
PROMPT_LEN = 8
MAX_TOKENS = 32
ARRIVAL_GAP_S = 0.01
WINDOWS = 2  # best-of per side: robust to one scheduler stall on a shared box


def _model():
    import jax

    from ray_tpu.models.gptj import GPTJConfig, gptj_init

    cfg = GPTJConfig(
        vocab_size=256, seq_len=128, d_model=128, n_layers=4, n_heads=4,
        rotary_dim=16, dtype="float32", remat=False, attn_impl="xla",
        fused_loss=False,
    )
    return cfg, gptj_init(jax.random.PRNGKey(0), cfg)


def run_bench() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm import EngineConfig, LLMEngine, SamplingParams
    from ray_tpu.models.gptj import gptj_decode

    cfg, params = _model()
    rng = np.random.RandomState(0)
    prompts = [
        list(rng.randint(0, cfg.vocab_size, PROMPT_LEN)) for _ in range(N_REQUESTS)
    ]
    arrivals = [i * ARRIVAL_GAP_S for i in range(N_REQUESTS)]
    total_tokens = N_REQUESTS * MAX_TOKENS

    # -- static baseline: sequential gptj_decode per request ---------------
    decode = jax.jit(
        lambda p, t: gptj_decode(cfg, p, t, MAX_TOKENS), static_argnums=()
    )
    warm = decode(params, jnp.asarray([prompts[0]], jnp.int32))
    int(warm[0, -1])  # compile + transfer barrier before timing

    def run_static():
        t0 = time.perf_counter()
        out = []
        for arr, prompt in zip(arrivals, prompts):
            now = time.perf_counter() - t0
            if now < arr:
                time.sleep(arr - now)
            toks = decode(params, jnp.asarray([prompt], jnp.int32))
            # the per-request host sync IS the static baseline being
            # measured: sequential whole-completion decode was the
            # pre-ray_tpu.llm serving story this bench compares against
            out.append(list(np.asarray(toks)[0, PROMPT_LEN:]))  # raylint: disable=RL006
        return time.perf_counter() - t0, out

    static_wall, static_out = min(
        (run_static() for _ in range(WINDOWS)), key=lambda r: r[0]
    )
    static_tps = total_tokens / static_wall

    # -- continuous engine -------------------------------------------------
    # table width sized to the workload: decode cost scales with the table
    # width, not the live length, so an over-provisioned table would tax
    # every step
    blocks_per_seq = -(-(PROMPT_LEN + MAX_TOKENS) // 8)
    engine = LLMEngine(
        cfg, params,
        EngineConfig(
            max_slots=N_REQUESTS, block_size=8,
            num_blocks=N_REQUESTS * blocks_per_seq + 2,
            max_blocks_per_seq=blocks_per_seq, prefill_chunk=PROMPT_LEN,
        ),
    )
    engine.warmup()  # compile the step jits outside the timed windows

    def run_continuous():
        t0 = time.perf_counter()
        reqs = []
        pending = list(zip(arrivals, prompts))
        while pending or not all(r.finished for r in reqs):
            now = time.perf_counter() - t0
            while pending and pending[0][0] <= now:
                _, prompt = pending.pop(0)
                reqs.append(
                    engine.submit(prompt, SamplingParams(max_tokens=MAX_TOKENS))
                )
            if not engine.step():
                time.sleep(0.0005)
        return time.perf_counter() - t0, [r.out for r in reqs]

    cont_wall, cont_out = min(
        (run_continuous() for _ in range(WINDOWS)), key=lambda r: r[0]
    )
    cont_tps = total_tokens / cont_wall

    # greedy determinism: both paths must produce identical tokens, or the
    # throughput comparison is comparing different work
    assert cont_out == static_out, "continuous/static token mismatch"

    return {
        "metric": "llm_continuous_batching_tokens_per_sec",
        "value": round(cont_tps, 1),
        "unit": "tokens/s",
        "vs_baseline": round(cont_tps / static_tps, 3),
        "detail": {
            "static_tokens_per_sec": round(static_tps, 1),
            "requests": N_REQUESTS,
            "max_tokens": MAX_TOKENS,
            "arrival_gap_s": ARRIVAL_GAP_S,
            "static_wall_s": round(static_wall, 3),
            "continuous_wall_s": round(cont_wall, 3),
            "preemptions": engine.stats()["preemptions"],
        },
    }


# -- speculative decoding ----------------------------------------------------

SPEC_K = 3
SPEC_SLOTS = 4
SPEC_PROMPT_LEN = 16
# prompt seeds chosen (scanned offline) so the tiny model's greedy
# continuation of the patterned prompt goes periodic within ~8 tokens —
# the structured/templated-output regime prompt-lookup drafting targets
REPETITIVE_SEEDS = (1, 13, 22, 36)
ADVERSARIAL_SEEDS = (100, 101, 102, 103)


def _spec_model():
    import jax

    from ray_tpu.models.gptj import GPTJConfig, gptj_init

    cfg = GPTJConfig(
        vocab_size=256, seq_len=256, d_model=128, n_layers=4, n_heads=4,
        rotary_dim=16, dtype="float32", remat=False, attn_impl="xla",
        fused_loss=False,
    )
    return cfg, gptj_init(jax.random.PRNGKey(1), cfg)


def run_spec_bench(smoke: bool = False) -> dict:
    import numpy as np

    from ray_tpu.llm import EngineConfig, LLMEngine, SamplingParams

    cfg, params = _spec_model()
    windows = 1 if smoke else WINDOWS
    mt_rep = 24 if smoke else 64
    # the adversarial run stays 16 tokens even in smoke: shorter runs sit
    # entirely inside the backoff ramp and overstate the regression
    mt_adv = 16

    def patterned(seed):
        pat = list(np.random.RandomState(seed).randint(0, cfg.vocab_size, 4))
        return (pat * 8)[:SPEC_PROMPT_LEN]

    def random_prompt(seed):
        return list(
            np.random.RandomState(seed).randint(0, cfg.vocab_size, SPEC_PROMPT_LEN)
        )

    rep_prompts = [patterned(s) for s in REPETITIVE_SEEDS]
    adv_prompts = [random_prompt(s) for s in ADVERSARIAL_SEEDS]
    mt_max = max(mt_rep, mt_adv)

    def make_engine(spec_k):
        bps = -(-(SPEC_PROMPT_LEN + mt_max + SPEC_K + 1) // 8)
        return LLMEngine(
            cfg, params,
            EngineConfig(
                max_slots=SPEC_SLOTS, block_size=8,
                num_blocks=SPEC_SLOTS * bps + 2, max_blocks_per_seq=bps,
                prefill_chunk=SPEC_PROMPT_LEN, spec_k=spec_k,
            ),
        )

    def run(engine, prompts, mt):
        reqs = [engine.submit(p, SamplingParams(max_tokens=mt)) for p in prompts]
        t0 = time.perf_counter()
        while not all(r.finished for r in reqs):
            engine.step()
        return time.perf_counter() - t0, [r.out for r in reqs]

    base = make_engine(0)
    base.warmup()  # compile outside the timed windows
    spec = make_engine(SPEC_K)
    spec.warmup()  # both step paths: verify AND the backoff fallback

    results = {}
    for name, prompts, mt in (
        ("repetitive", rep_prompts, mt_rep),
        ("adversarial", adv_prompts, mt_adv),
    ):
        bt, bout = min(
            (run(base, prompts, mt) for _ in range(windows)), key=lambda r: r[0]
        )
        s0 = spec.stats()
        st, sout = min(
            (run(spec, prompts, mt) for _ in range(windows)), key=lambda r: r[0]
        )
        s1 = spec.stats()
        # greedy speculative decode must be token-identical to the plain
        # engine, or the throughput comparison is comparing different work
        assert sout == bout, f"spec/non-spec token mismatch on {name}"
        total = len(prompts) * mt
        results[name] = {
            "baseline_tokens_per_sec": round(total / bt, 1),
            "spec_tokens_per_sec": round(total / st, 1),
            "speedup": round(bt / st, 3),
            "acceptance_rate": round(
                (s1["spec_accepted"] - s0["spec_accepted"])
                / max(s1["spec_proposed"] - s0["spec_proposed"], 1),
                3,
            ),
            "drafter_overhead_s": round(
                s1["spec_draft_seconds"] - s0["spec_draft_seconds"], 4
            ),
        }
    return {
        "metric": "llm_speculative_decode_speedup",
        "value": results["repetitive"]["spec_tokens_per_sec"],
        "unit": "tokens/s",
        "vs_baseline": results["repetitive"]["speedup"],
        "detail": {
            **results,
            "drafter": "ngram",
            "spec_k": SPEC_K,
            "requests": SPEC_SLOTS,
            "smoke": smoke,
        },
    }


# -- cross-request prefix cache ----------------------------------------------

PREFIX_SHARED_LEN = 256   # the common system-prompt/few-shot head
PREFIX_SUFFIX_LEN = 16    # per-request distinct tail
PREFIX_N = 8
PREFIX_MAX_TOKENS = 8
PREFIX_BLOCK = 16


def run_prefix_bench(smoke: bool = False) -> dict:
    """Shared-system-prompt workload: request 0 is COLD (it populates the
    radix tree), requests 1..N-1 are WARM (their 256-token head matches).
    Requests run one at a time so each TTFT is a clean prefill+first-step
    measurement, not a batching artifact.  Reported: prefill tokens
    actually computed (engine counter) and mean warm TTFT, cache on vs
    off, with token-identity asserted between the arms."""
    import numpy as np

    from ray_tpu.llm import EngineConfig, LLMEngine, SamplingParams

    cfg, params = _spec_model()
    shared_len = 128 if smoke else PREFIX_SHARED_LEN
    n_req = 4 if smoke else PREFIX_N
    rng = np.random.RandomState(7)
    shared = list(rng.randint(0, cfg.vocab_size, shared_len))
    prompts = [
        shared + list(rng.randint(0, cfg.vocab_size, PREFIX_SUFFIX_LEN))
        for _ in range(n_req)
    ]
    total = shared_len + PREFIX_SUFFIX_LEN + PREFIX_MAX_TOKENS
    bps = -(-(total + 1) // PREFIX_BLOCK)

    def make_engine(cached: bool):
        e = LLMEngine(
            cfg, params,
            EngineConfig(
                max_slots=2, block_size=PREFIX_BLOCK,
                # room for the resident shared prefix + two live tables
                num_blocks=2 * bps + shared_len // PREFIX_BLOCK + 4,
                max_blocks_per_seq=bps, prefill_chunk=32,
                prefix_cache=cached,
            ),
        )
        e.warmup()
        return e

    def run(engine):
        outs, ttfts = [], []
        p0 = engine.stats()["prefill_tokens_computed"]
        for prompt in prompts:
            req = engine.submit(prompt, SamplingParams(max_tokens=PREFIX_MAX_TOKENS))
            while not req.finished:
                engine.step()
            outs.append(list(req.out))
            ttfts.append(req.first_token_t - req.arrival_t)
        prefill = engine.stats()["prefill_tokens_computed"] - p0
        return outs, ttfts, prefill

    on_out, on_ttft, on_prefill = run(make_engine(True))
    off_out, off_ttft, off_prefill = run(make_engine(False))
    # prefix reuse must be EXACT — or the TTFT comparison is meaningless
    assert on_out == off_out, "prefix-cache on/off token mismatch"
    warm_on = sum(on_ttft[1:]) / max(len(on_ttft) - 1, 1)
    warm_off = sum(off_ttft[1:]) / max(len(off_ttft) - 1, 1)
    return {
        "metric": "llm_prefix_cache_warm_ttft_speedup",
        "value": round(warm_off / max(warm_on, 1e-9), 3),
        "unit": "x",
        "vs_baseline": round(warm_off / max(warm_on, 1e-9), 3),
        "detail": {
            "requests": n_req,
            "shared_prefix_tokens": shared_len,
            "prefill_tokens_on": int(on_prefill),
            "prefill_tokens_off": int(off_prefill),
            "prefill_reduction": round(1.0 - on_prefill / max(off_prefill, 1), 3),
            "ttft_cold_on_s": round(on_ttft[0], 4),
            "ttft_warm_on_s": round(warm_on, 4),
            "ttft_warm_off_s": round(warm_off, 4),
            "smoke": smoke,
        },
    }


MULTICHIP_N = 6
MULTICHIP_MAX_TOKENS = 24


def run_multichip_bench(smoke: bool = False) -> dict:
    """Paired single-chip vs tensor-parallel engines on one workload:
    every arm must emit identical greedy tokens (asserted — otherwise
    the throughput comparison compares different work).  Reported per
    mesh size: aggregate tokens/s, mean TTFT, per-device KV-pool bytes
    (the ledger's per-device attribution — the pool splits 1/tp)."""
    import jax
    import numpy as np

    from ray_tpu.llm import EngineConfig, LLMEngine, SamplingParams

    n_dev = len(jax.devices())
    tps = [t for t in (2, 4) if t <= n_dev]
    if not tps:
        raise RuntimeError(
            f"the multichip bench compares tp arms and needs >=2 devices; "
            f"jax found {n_dev} (on a CPU host, set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=4 before jax "
            "initializes)"
        )

    cfg, params = _model()
    n_req = 3 if smoke else MULTICHIP_N
    mt = 12 if smoke else MULTICHIP_MAX_TOKENS
    rng = np.random.RandomState(11)
    prompts = [
        list(rng.randint(0, cfg.vocab_size, PROMPT_LEN)) for _ in range(n_req)
    ]

    def run(tp):
        eng = LLMEngine(
            cfg, params,
            EngineConfig(
                max_slots=4, num_blocks=64, block_size=8,
                max_blocks_per_seq=16, prefill_chunk=16, tp=tp,
            ),
        )
        eng.warmup()  # jit outside the measured window
        reqs = [eng.submit(p, SamplingParams(max_tokens=mt)) for p in prompts]
        t0 = time.perf_counter()
        while not all(r.finished for r in reqs):
            eng.step()
        dt = time.perf_counter() - t0
        ttft = sum(r.first_token_t - r.arrival_t for r in reqs) / len(reqs)
        led = eng.hbm_ledger()
        kv_per_dev = {
            dev: row["pool_bytes"]
            for dev, row in led.get("per_device", {}).items()
        } or {"0": led["pool_bytes"]}
        return (
            [list(r.out) for r in reqs],
            (n_req * mt) / dt,
            ttft,
            kv_per_dev,
        )

    base_out, base_tps, base_ttft, base_kv = run(1)
    arms = {
        "tp1": {
            "tokens_per_sec": round(base_tps, 2),
            "ttft_s": round(base_ttft, 4),
            "kv_pool_bytes_per_device": base_kv,
        }
    }
    best = base_tps
    for tp in tps:
        out, toks, ttft, kv = run(tp)
        assert out == base_out, f"tp={tp} token mismatch vs single-chip"
        arms[f"tp{tp}"] = {
            "tokens_per_sec": round(toks, 2),
            "ttft_s": round(ttft, 4),
            "kv_pool_bytes_per_device": kv,
        }
        best = toks
    return {
        "metric": "llm_multichip_tp_tokens_per_sec",
        "value": round(best, 2),
        "unit": "tok/s",
        "vs_baseline": round(best / max(base_tps, 1e-9), 3),
        "detail": {
            "requests": n_req,
            "max_tokens": mt,
            "mesh_sizes": tps,
            "arms": arms,
            "substrate": jax.default_backend(),
            "smoke": smoke,
        },
    }


def run_loadgen_bench(smoke: bool = False) -> dict:
    """Open-loop load harness over the served HTTP path (``llm.loadgen``):
    healthy / overload / replica-kill arms against a tiny 2-replica app,
    client-side percentiles joined with the server-side phase ledgers.
    The headline is the healthy-arm p99; ``vs_baseline`` carries the
    phase-sum identity fraction (1.0 = every attributed request's phases
    sum to its end-to-end latency within ε)."""
    from ray_tpu.llm import loadgen

    report = loadgen.run_report(smoke=smoke)
    healthy = report["arms"]["healthy"]["client"]
    ident = report["identity"]
    return {
        "metric": "llm_loadgen_healthy_p99_s",
        "value": healthy["e2e_s"].get("p99") or 0.0,
        "unit": "s",
        "vs_baseline": ident["within_eps_frac"] or 0.0,
        "detail": report,
    }


def main(argv=None) -> list:
    import argparse
    import os

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--smoke", action="store_true",
        help="shrunken workloads for CI (seconds, looser signal)",
    )
    ap.add_argument(
        "--only",
        choices=("all", "serving", "continuous", "spec", "prefix",
                 "multichip", "loadgen"),
        default="all",
        help="run a subset instead of the full set (bench.py's llm_serving "
        "section uses --only serving, its llm_prefix section --only prefix "
        "and its multichip section --only multichip, so none pays for the "
        "others' workloads)",
    )
    args = ap.parse_args(argv)
    benches = {
        "continuous": run_bench,
        "spec": lambda: run_spec_bench(smoke=args.smoke),
        "prefix": lambda: run_prefix_bench(smoke=args.smoke),
        "multichip": lambda: run_multichip_bench(smoke=args.smoke),
        "loadgen": lambda: run_loadgen_bench(smoke=args.smoke),
    }
    groups = {
        # loadgen boots a whole serve cluster — it runs only when asked
        "all": [n for n in benches if n != "loadgen"],
        "serving": ["continuous", "spec"],
    }
    names = groups.get(args.only, [args.only])
    if "multichip" in names \
            and "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        # the tp arms need a host-device mesh; XLA reads this flag at
        # first backend init (lazy, none of the benches has run yet), so
        # bootstrap it here rather than ask every caller to export it
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4"
        ).strip()
    records = []
    for name in names:
        rec = benches[name]()
        print(json.dumps(rec), flush=True)
        records.append(rec)
    return records


if __name__ == "__main__":
    main()
