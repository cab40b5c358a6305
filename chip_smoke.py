"""chip_smoke.py — the standing proof that the system starts on the chip.

    python chip_smoke.py                    # one TPU chip: every phase
    python chip_smoke.py --phase serve --tp 4    # four chips: serve only
    python chip_smoke.py --cpu-rehearsal    # control flow only, tiny sizes

Drives both device programs once through the entry points a user calls,
at the published widths of models the repo supports, with seeded random
weights:

* **serve** — ``ray_tpu.init()`` → ``serve.run(build_llm_app(model="gptj",
  ...), http=True)`` → streamed requests over the HTTP proxy.  GPT-J at
  d_model 4096, 16 heads x 256, rotary 64, vocab 50400, bf16, full depth.
* **kernels** — each Pallas kernel compiled by Mosaic against its XLA
  reference at the serve and train shapes.
* **train** — ``JaxTrainer(..., ScalingConfig(num_workers=1))`` whose loop
  builds ``build_train_step`` over ``make_mesh`` on the worker's devices
  and takes 5 steps of the 406M GPT.

A chip belongs to one process at a time.  This parent never initializes a
JAX backend (it does not import jax); the probe and every phase run in one
child each, and the parent does not start the next until the last one and
everything it started are gone.  No phase is wrapped in a try/except: the
first failure ends the run with a non-zero exit code and no result line.

On a machine without a TPU the run ends at the probe, non-zero.
``--cpu-rehearsal`` exists to debug the control flow on a CPU at tiny
sizes; it can never print the result line.

Every stdout line that carries a number is one JSON object naming the
platform, device_kind, device count and jax/jaxlib/libtpu versions of the
process that measured it.  Seconds printed here are set-up time (weight
generation, compilation); rates and utilizations belong to the benchmark.
The last stdout line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import ctypes
import json
import os
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chip_smoke_out")
PHASES = ("serve", "kernels", "train")

#: GPT-J depth served on ONE 16 GB chip.  Full depth: 28 layers of bf16
#: weights are 12.10 GB, the KV pool below 1.88 GB, and each step's
#: transient copy of the pool (ROADMAP S2) another 1.88 GB — inside the
#: chip's 16.9e9-byte limit.  Cut this only if the chip's memory refuses.
SERVE_DEPTH = 28
#: bf16 keeps 8 significant bits: a kernel may differ from its reference
#: by 4 units in the last place of the largest reference magnitude
BF16_TOL = 2.0**-6
#: back-to-back calls behind each ``kernel_ms`` of the kernel phase
TIMED_CALLS = 20


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def emit(phase: str, device: dict, **fields) -> None:
    """One JSON stdout line; ``device`` names where the numbers came from."""
    print(json.dumps({"phase": phase, "device": device, **fields}), flush=True)


def note(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


def device_line(rep: dict) -> dict:
    """The identity every line carries, from a ``device_report()``."""
    return {
        "platform": rep["platform"],
        "device_kind": rep["device_kind"],
        "count": rep["device_count"],
        **rep["versions"],
    }


# ---------------------------------------------------------------------------
# probe (child): is there a chip at all?
# ---------------------------------------------------------------------------


def phase_probe(_args) -> None:
    import jax

    d = jax.devices()
    print(json.dumps({
        "platform": d[0].platform, "kind": d[0].device_kind, "count": len(d),
    }), flush=True)


# ---------------------------------------------------------------------------
# serve (child = driver; the replica worker is the one process on the chip)
# ---------------------------------------------------------------------------


def _serve_requests(vocab: int, rehearsal: bool) -> dict:
    """The eight requests, from a seed.  Lengths are a quarter the size in
    rehearsal; the structure (shared prefix diverging mid-block, periodic
    prompt, repeats) is the same."""
    import numpy as np

    rng = np.random.RandomState(0)
    k = 4 if rehearsal else 1

    def rand(n):
        return [int(t) for t in rng.randint(1, vocab, size=n // k)]

    # a whole number of 16-token blocks plus 8, then a tail that completes
    # the last block: the fork's prompt leaves the cold one's MID-block
    head = rand(136) if not rehearsal else rand(4 * 40)
    cold = head + (rand(40) if not rehearsal else rand(4 * 24))
    period = rand(28)[:7]
    seeded = dict(temperature=0.8, top_k=40, top_p=0.95, seed=1234)
    sampled = rand(200)
    return {
        "short_greedy": dict(prompt=rand(64), max_tokens=32 // k),
        "sampled": dict(prompt=sampled, max_tokens=48 // k, **seeded),
        "long_greedy": dict(prompt=rand(512), max_tokens=64 // k),
        "prefix_cold": dict(prompt=cold, max_tokens=32 // k),
        "prefix_hit": dict(prompt=cold, max_tokens=32 // k),
        "prefix_fork": dict(prompt=head + rand(40 * k), max_tokens=32 // k),
        "periodic": dict(prompt=(period * 14)[: 96 // k], max_tokens=48 // k),
        "sampled_again": dict(prompt=sampled, max_tokens=48 // k, **seeded),
    }


def _first_difference(a: list, b: list):
    """Index of the first position where two token lists differ, or None."""
    return next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)


def _stream(port: int, app: str, payload: dict) -> list:
    """POST one request to the proxy and read the chunked token stream."""
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        conn.request(
            "POST", f"/{app}", body=json.dumps(payload),
            headers={"content-type": "application/json"},
        )
        resp = conn.getresponse()
        if resp.status != 200:
            raise SmokeFailure(f"HTTP {resp.status}: {resp.read()[:500]!r}")
        return [json.loads(line) for line in resp if line.strip()]
    finally:
        conn.close()


def _serve(args) -> None:
    import dataclasses
    from concurrent.futures import ThreadPoolExecutor

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private import shm_store
    from ray_tpu.llm import EngineConfig
    from ray_tpu.models.gptj import GPTJ_6B, GPTJConfig
    from ray_tpu.serve.llm import build_llm_app

    tp = args.tp
    if args.cpu_rehearsal:
        cfg = GPTJConfig(
            vocab_size=512, seq_len=256, d_model=64, n_layers=2, n_heads=4,
            rotary_dim=8, dtype="float32",
        )
        ecfg = EngineConfig(
            max_slots=4, num_blocks=64, block_size=16, max_blocks_per_seq=12,
            prefill_chunk=32, spec_k=3,
        )
    else:
        cfg = dataclasses.replace(GPTJ_6B, n_layers=SERVE_DEPTH)
        # 40 blocks x 16 cover the longest request (512 + 64) plus the
        # speculation window; 256 blocks hold four of them and a warm tree
        ecfg = EngineConfig(
            max_slots=4, num_blocks=256, block_size=16, max_blocks_per_seq=40,
            prefill_chunk=128, spec_k=3,
        )

    t0 = time.time()
    handle = serve.run(
        build_llm_app(
            model="gptj", model_cfg=cfg, engine_config=ecfg, seed=0,
            tp=tp if tp > 1 else None,
        ),
        name="llm", http=True, http_port=0,
    )
    ready_s = round(time.time() - t0, 1)
    controller = ray_tpu.get_actor("SERVE_CONTROLLER")
    port = ray_tpu.get(controller.get_proxy_port.remote(), timeout=30)
    before = handle.device_report.remote().result()
    dev = device_line(before)
    if not args.cpu_rehearsal:
        check(dev["platform"] == "tpu", f"replica computes on {dev['platform']}")
    check(dev["count"] >= tp, f"tp={tp} but the replica sees {dev['count']} devices")
    emit(
        "serve", dev, event="replica_ready", model="gptj", depth=cfg.n_layers,
        d_model=cfg.d_model, n_heads=cfg.n_heads, vocab=cfg.vocab_size,
        dtype=cfg.dtype, tp=tp, serve_run_ready_s=ready_s,
        step_first_call_s=before["first_call_s"],
        compile_cache=before["compile_cache"],
        object_store="native_arena" if shm_store._write_arena_name
        else "python_segments",
    )

    reqs = _serve_requests(cfg.vocab_size, args.cpu_rehearsal)
    out: dict = {}
    # Two requests share the engine's steps first; after them every
    # request runs alone, so that requests compared for identity take the
    # same programs both times (whenever ANY running slot's drafter finds
    # a match, every slot's step goes through the verify program instead
    # of the decode program) and differ in ONE thing: cold or prefix hit.
    # Order matters too: the cold prompt has finished (its blocks are in
    # the radix tree) before its repeat and its fork arrive.
    waves = (
        ("short_greedy", "long_greedy"),
        ("prefix_cold",), ("sampled",), ("prefix_hit",), ("prefix_fork",),
        ("sampled_again",), ("periodic",),
    )
    with ThreadPoolExecutor(max_workers=2) as pool:
        for wave in waves:
            futs = {n: pool.submit(_stream, port, "llm", reqs[n]) for n in wave}
            for n, f in futs.items():
                out[n] = f.result()
    for n, toks in out.items():
        check(
            len(toks) == reqs[n]["max_tokens"],
            f"{n}: asked {reqs[n]['max_tokens']} tokens, got {len(toks)}",
        )
        check(
            all(isinstance(t, int) and 0 <= t < cfg.vocab_size for t in toks),
            f"{n}: token outside the vocabulary",
        )
    # The same prompt asked twice — cold, then as a prefix hit — gives the
    # same tokens, bit for bit, at every tp: the hit recomputes the prompt's
    # tail at other rows of the prefill chunk, and no step may round a row
    # by where it sits (llm.multichip._tp_sum exists for this check).
    diverged = {
        "prefix_hit_vs_cold": _first_difference(out["prefix_hit"], out["prefix_cold"]),
        "sampled_again_vs_sampled": _first_difference(
            out["sampled_again"], out["sampled"]
        ),
    }
    check(
        diverged["prefix_hit_vs_cold"] is None,
        "same prompt, cold then prefix hit, gave different tokens from output "
        f"index {diverged['prefix_hit_vs_cold']}",
    )
    check(
        diverged["sampled_again_vs_sampled"] is None,
        "same seeded request asked twice gave different tokens from output "
        f"index {diverged['sampled_again_vs_sampled']}",
    )

    stats = handle.stats.remote().result()
    audits = handle.audit.remote().result()
    after = handle.device_report.remote().result()
    pc = stats["prefix_cache"]
    check(pc["hit_tokens"] > 0, "prefix cache never hit")
    check(pc["cow_forks"] > 0, "no copy-on-write fork on the mid-block divergence")
    check(stats["spec_proposed"] > 0, "the n-gram drafter never proposed")
    check(stats["retraces"] == 0, f"{stats['retraces']} retraces after warm-up")
    check(audits["pool"]["ok"], f"KV pool audit failed: {audits['pool']}")
    check(audits["prefix_cache"]["ok"], f"prefix audit: {audits['prefix_cache']}")
    check(
        all(s["cache_size"] == 1 for s in after["jit_sites"].values()),
        f"retrace probe: jit cache sizes {after['jit_sites']} (want 1 each)",
    )
    att = after["attention"]
    if not args.cpu_rehearsal:
        # the rule's answer must be what the compiled steps contain
        check(att["auto_rule"] == "pallas", f"auto rule says {att['auto_rule']}")
        for site in ("decode", "verify"):
            check(
                att["mosaic_kernels"][site] == [f"paged_attention_{site}"],
                f"{site} step holds kernels {att['mosaic_kernels'][site]}",
            )
    per_device = after["hbm"].get("per_device", {})
    if tp > 1:
        # nothing may hold the whole pool or the whole parameter tree
        whole = after["hbm"]["params_bytes"]
        for d, row in per_device.items():
            check(
                row["pool_bytes"] * tp == after["hbm"]["pool_bytes"],
                f"device {d} holds {row['pool_bytes']} pool bytes",
            )
            check(
                row["params_bytes"] < whole,
                f"device {d} holds {row['params_bytes']} of {whole} param bytes",
            )
        peaks = [m.get("peak_bytes_in_use", 0) for m in after["memory"].values()]
        check(
            max(peaks) < 1.5 * min(peaks) or args.cpu_rehearsal,
            f"uneven device memory, peaks {peaks}",
        )
    emit(
        "serve", dev, event="served", requests=len(out),
        tokens_out={n: len(t) for n, t in out.items()},
        prompt_len={n: len(r["prompt"]) for n, r in reqs.items()},
        identical_tokens=sorted(diverged),
        attention=att, hit_tokens=pc["hit_tokens"], cow_forks=pc["cow_forks"],
        spec_proposed=stats["spec_proposed"],
        spec_accepted=stats["spec_accepted"], retraces=stats["retraces"],
        preemptions=stats["preemptions"], audits_ok=True,
        hbm_params_bytes=after["hbm"]["params_bytes"],
        hbm_pool_bytes=after["hbm"]["pool_bytes"],
        hbm_per_device=per_device, memory=after["memory"],
        compile_cache=after["compile_cache"],
    )

    import jax._src.xla_bridge as xb

    check(not xb._backends, f"the driver initialised backends {list(xb._backends)}")


def phase_serve(args) -> None:
    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init()
    try:
        _serve(args)
    finally:  # pass or fail, the replica lets go of the chip
        serve.shutdown()
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# kernels (child, on the chip itself)
# ---------------------------------------------------------------------------


def phase_kernels(args) -> None:
    import jax
    import jax.numpy as jnp

    from ray_tpu._private.compile_cache import ensure_compile_cache
    from ray_tpu.ops import attention, paged_attention as pa
    from ray_tpu.util.device_prof import device_report, mosaic_kernels

    ensure_compile_cache()
    dev = device_line(device_report())
    on_chip = not args.cpu_rehearsal
    if on_chip:
        check(dev["platform"] == "tpu", f"kernel phase runs on {dev['platform']}")
    dt = jnp.bfloat16 if on_chip else jnp.float32
    tol = BF16_TOL if on_chip else 1e-5

    def rnd(i, shape):
        return jax.random.normal(jax.random.PRNGKey(i), shape, jnp.float32).astype(dt)

    def compare(name, kernel_fn, ref_fn, operands, want_kernels):
        lowered = jax.jit(kernel_fn).lower(*operands)
        kernels = sorted(mosaic_kernels(lowered))
        if on_chip:
            # an interpreted pallas_call lowers to plain ops: no kernel here
            check(kernels == sorted(want_kernels), f"{name}: holds {kernels}")
        run = jax.jit(kernel_fn)
        got = jax.tree_util.tree_leaves(run(*operands))
        ref = jax.tree_util.tree_leaves(jax.jit(ref_fn)(*operands))
        t0 = time.perf_counter()
        for _ in range(TIMED_CALLS):
            out = run(*operands)
        jax.block_until_ready(out)
        kernel_ms = (time.perf_counter() - t0) / TIMED_CALLS * 1e3
        errs, bounds = [], []
        for g, r in zip(got, ref):
            g, r = g.astype(jnp.float32), r.astype(jnp.float32)
            check(bool(jnp.isfinite(g).all()), f"{name}: non-finite output")
            errs.append(float(jnp.abs(g - r).max()))
            bounds.append(tol * max(1.0, float(jnp.abs(r).max())))
        check(
            all(e <= b for e, b in zip(errs, bounds)),
            f"{name}: max abs error {errs} over tolerance {bounds}",
        )
        emit(
            "kernels", dev, kernel=name, mosaic_kernels=kernels,
            dtype=str(jnp.dtype(dt)), max_abs_err=errs, tolerance=bounds,
            # the host's clock over back-to-back calls of the compiled
            # program, dispatch included; off the chip no time is reported
            kernel_ms=round(kernel_ms, 4) if on_chip else "not measured",
        )

    # paged decode + verify at the serve phase's shapes, then at the local
    # head count of tp=4; the third row is the dispatch rule's boundary, the
    # last a tp=4 decode batch as the engine sends it: 64 slots x table 128,
    # most of them empty (an empty slot feeds position 0 and holds one block).
    # Lengths are ragged in every row: one token, a block plus one, half a
    # table, a table less the window.
    # Both paths read the pool the way the jitted steps hand it over
    # (model_runner._layer_loop): every layer's blocks in one (layers * nb,
    # ...) view, the tables offset to a layer that is not the first
    w = 4
    layers, layer = 3, 2
    paged_cases = (
        (16, 16, 256, 4, 40, 256), (4, 16, 256, 4, 40, 256),
        (16, 8, 128, 4, 40, 256), (4, 16, 256, 64, 128, 1024),
    )
    for heads, bs, d, slots, tmax, nb in paged_cases if on_chip else ((2, 4, 16, 4, 6, 24),):
        impl = "auto" if on_chip else "pallas"
        if on_chip:
            check(pa.auto_impl(bs, d) == "pallas", f"auto rule at {bs}x{d}")
        kp = rnd(1, (layers * nb, heads, bs, d))
        vp = rnd(2, (layers * nb, heads, bs, d))
        tables = layer * nb + jax.random.randint(
            jax.random.PRNGKey(3), (slots, tmax), 1, nb
        )
        cap = tmax * bs
        ragged = [0, bs + 1, cap // 2 + 3, cap - w - 1]
        # the four ragged rows spread over the slots, position 0 between them
        base = jnp.zeros(slots, jnp.int32).at[
            jnp.arange(4) * (slots // 4)
        ].set(jnp.array(ragged, jnp.int32))
        positions = base[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
        shape = f"h{heads}_b{bs}_d{d}_s{slots}_t{tmax}"
        compare(
            f"paged_decode_{shape}",
            lambda q, k, v, t, n: pa.paged_attention(q, k, v, t, n, impl=impl),
            pa.paged_attention_xla,
            (rnd(4, (slots, heads, d)), kp, vp, tables, base + 1),
            ["paged_attention_decode"],
        )
        compare(
            f"paged_verify_{shape}",
            lambda q, k, v, t, p: pa.paged_verify_attention(q, k, v, t, p, impl=impl),
            pa.paged_verify_attention_xla,
            (rnd(5, (slots, w, heads, d)), kp, vp, tables, positions),
            ["paged_attention_verify"],
        )

    # the retention decode kernel at Brumby's widths (8 key-value heads of
    # 128, 5 query heads each) on a pool of 8 float32 states: six rows, four
    # live, a dead row naming a live row's slot (it must move nothing)
    from ray_tpu.ops import power_retention as pr

    d, kv, group = (128, 8, 5) if on_chip else (16, 2, 2)
    slots = jnp.asarray([5, 0, 0, 2, 7, 1], jnp.int32)
    live = jnp.asarray([True, False, True, True, False, True])
    log_g = -jax.random.uniform(jax.random.PRNGKey(6), (6, kv), jnp.float32, 1e-3, 1e-2)

    def retention(impl):
        return lambda s, q, k, v: pr.retention_decode(
            s, q, k, v, log_g, slots, live, eps=1e-6, impl=impl)

    compare(
        f"retention_decode_h{kv}x{group}_d{d}",
        retention("auto" if on_chip else "pallas"), retention("xla"),
        (jax.random.normal(jax.random.PRNGKey(7), (8, kv) + pr.state_dims(d), jnp.float32),
         rnd(8, (6, kv * group, d)), rnd(9, (6, kv, d)), rnd(10, (6, kv, d))),
        ["retention_decode"],
    )

    # latent attention at Kimi-K2.5's widths: 64 heads over ONE row of 512 +
    # 64 lanes (padded to 640) a token, blocks of 128, a table of 138: the
    # absorbed decode kernel against its XLA path (ragged rows as above), and
    # the expert layer's tiles against every held expert on every row
    from ray_tpu.ops import latent_attention as la, moe

    heads, rank, rope, bs, slots, tmax, nb = (
        (64, 512, 64, 128, 16, 138, 600) if on_chip else (4, 128, 8, 4, 4, 6, 24))
    width = la.padded_width(rank, rope)
    if on_chip:
        check(la.auto_impl(bs, width, rank) == "pallas", "latent auto rule")
    rows_pool = rnd(11, (layers * nb, bs, width)).at[..., rank + rope:].set(0)
    tables = layer * nb + jax.random.randint(jax.random.PRNGKey(12), (slots, tmax), 1, nb)
    cap = tmax * bs
    at = jnp.zeros(slots, jnp.int32).at[jnp.arange(4) * (slots // 4)].set(
        jnp.array([0, bs + 1, cap // 2 + 3, cap - 1], jnp.int32))
    q_abs = (rnd(13, (slots, heads, width)) * width**-0.25).at[..., rank + rope:].set(0)

    def latent(impl):
        return lambda q, pool, t, p: la.latent_decode_attention(
            q, pool, t, p, rank=rank, scale=width**-0.5, impl=impl)

    compare(
        f"latent_decode_h{heads}_r{rank}+{rope}_b{bs}_s{slots}_t{tmax}",
        latent("auto" if on_chip else "pallas"), latent("xla"),
        (q_abs, rows_pool, tables, at), ["latent_attention_decode"],
    )
    # the chunk's flash kernel against the dense softmax: 512 queries that
    # start at key 0, inside a block and inside a key tile, and where the
    # cell's chunks do (16,384: 21 key tiles every query sees whole take the
    # path without a mask, the 22nd the masked one), over the same table
    c_len, dn, dv = (512, 128, 128) if on_chip else (8, 8, 16)
    for start in (0, 5 * bs + 3, 128 * bs) if on_chip else (bs + 1,):
        def chunk(impl, start=start):
            return lambda qn, qr, pool, wk, wv: la.latent_chunk_attention(
                qn, qr, pool, tables[0], start + jnp.arange(c_len, dtype=jnp.int32), wk, wv,
                rank=rank, scale=(dn + rope)**-0.5, impl=impl)

        compare(
            f"latent_chunk_h{heads}_c{c_len}_t{tmax}x{bs}_at{start}",
            chunk("auto" if on_chip else "pallas"), chunk("xla"),
            (rnd(20, (c_len, heads, dn)), rnd(21, (c_len, heads, rope)), rows_pool,
             rnd(22, (rank, heads, dn)) * rank**-0.5, rnd(23, (rank, heads, dv)) * rank**-0.5),
            ["latent_attention_chunk"],
        )
    d, f, held, n = (7168, 2048, 12, 16) if on_chip else (32, 16, 4, 6)
    mask = jax.random.bernoulli(jax.random.PRNGKey(14), 0.25, (n, held)).at[:, 1].set(False)
    wmat = jnp.where(mask, jax.random.uniform(jax.random.PRNGKey(15), (n, held)), 0.0)
    compare(
        f"moe_experts_d{d}_f{f}_held{held}_rows{n}",
        lambda x, g, u, dn: moe.expert_layer(x, mask, wmat, g, u, dn),
        lambda x, g, u, dn: sum(
            wmat[:, e:e + 1] * moe.swiglu(x, g[e], u[e], dn[e]) for e in range(held)),
        (rnd(16, (n, d)), rnd(17, (held, d, f)) * d**-0.5, rnd(18, (held, d, f)) * d**-0.5,
         rnd(19, (held, f, d)) * f**-0.5), [],
    )

    # flash forward + backward.  The train shape as the train step hands it
    # over: the fused projection's own (26, 1024, 3 x 1024) output, sixteen
    # heads of 64 read as column blocks of two heads, the gradient ONE
    # (26, 1024, 3072) array (``flash_attention_packed``).  Then tp=4's heads
    # through the head-major entry, which lays them out for the same kernels.
    from ray_tpu.ops.flash_attention import flash_attention_packed

    def flash_pair(name, flash, ref, operands, out_shape):
        do = rnd(9, out_shape).astype(jnp.float32)

        def grads(fn):
            return jax.grad(
                lambda *xs: (fn(*xs).astype(jnp.float32) * do).sum(),
                argnums=tuple(range(len(operands))))

        compare(f"flash_fwd_{name}", flash, ref, operands, ["flash_fwd"])
        compare(f"flash_bwd_{name}", grads(flash), grads(ref), operands,
                ["flash_bwd", "flash_fwd"])

    b, h, s, d = (26, 16, 1024, 64) if on_chip else (2, 2, 128, 16)
    if on_chip:
        check(attention.auto_impl(s) == "flash", f"auto rule at seq {s}")

    def dense_packed(qkv):
        q, k, v = (t.reshape(b, s, h, d).transpose(0, 2, 1, 3) for t in jnp.split(qkv, 3, axis=-1))
        return attention._xla_attention(q, k, v).transpose(0, 2, 1, 3).reshape(b, s, h * d)

    flash_pair(
        f"packed_b{b}_s{s}_w{3 * h * d}_h{h}", lambda qkv: flash_attention_packed(qkv, h),
        dense_packed, (rnd(6, (b, s, 3 * h * d)),), (b, s, h * d),
    )
    h = 4 if on_chip else h
    impl = "auto" if on_chip else "flash"
    flash_pair(
        f"b{b}_h{h}_s{s}_d{d}", lambda q, k, v: attention.causal_attention(q, k, v, impl=impl),
        attention._xla_attention, tuple(rnd(i, (b, h, s, d)) for i in (6, 7, 8)), (b, h, s, d),
    )


# ---------------------------------------------------------------------------
# train (child = driver; the one train worker is the process on the chip)
# ---------------------------------------------------------------------------


def _train_loop(config: dict) -> None:
    """``train_loop_per_worker``: 5 steps on one fixed batch."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import train
    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import build_train_step
    from ray_tpu.util.device_prof import device_report, mosaic_kernels

    cfg = GPTConfig(**config["model"])
    mesh = make_mesh(MeshConfig(dp=-1, fsdp=1, tp=1, sp=1), devices=jax.devices())

    traces = []

    def loss_fn(params, tokens):
        traces.append(None)  # this Python body runs only while jax traces
        return gpt_loss(cfg, params, tokens, mesh)

    init_fn, step_fn = build_train_step(loss_fn, optax.adamw(1e-4), mesh)
    t0 = time.time()
    state = init_fn(gpt_init(jax.random.PRNGKey(0), cfg))
    jax.block_until_ready(state)
    init_s = time.time() - t0
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (config["batch"], cfg.seq_len + 1), 0,
        cfg.vocab_size, jnp.int32,
    )
    kernels = sorted(mosaic_kernels(step_fn.lower(state, tokens)))
    losses, first_step_s, traced = [], None, None
    for _ in range(config["steps"]):
        t0 = time.time()
        state, loss = step_fn(state, tokens)
        losses.append(float(loss))  # host transfer: the step has finished
        if traced is None:
            first_step_s, traced = round(time.time() - t0, 1), len(traces)
    train.report({
        "losses": losses, "mosaic_kernels": kernels,
        "retraces": len(traces) - traced, "jit_cache_size": step_fn._cache_size(),
        "init_s": round(init_s, 1), "first_step_s": first_step_s,
        "device_report": device_report(),
        "n_params": sum(p.size for p in jax.tree_util.tree_leaves(state.params)),
    })


def _train(args) -> None:
    import math

    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    if args.cpu_rehearsal:
        model = dict(vocab_size=512, seq_len=128, d_model=64, n_layers=2,
                     n_heads=4, remat_policy="attn", ce_chunks=1)
        batch = 4
    else:
        # the 406M GPT of bench.py::_train_headline
        model = dict(vocab_size=50_304, seq_len=1024, d_model=1024,
                     n_layers=24, n_heads=16, remat_policy="attn", ce_chunks=1)
        batch = 26
    result = JaxTrainer(
        _train_loop,
        train_loop_config={"model": model, "batch": batch, "steps": 5},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            name="chip_smoke_train", storage_path=os.path.join(OUT_DIR, "train"),
        ),
    ).fit()
    if result.error is not None:
        raise result.error
    m = result.metrics
    dev = device_line(m["device_report"])
    losses = m["losses"]
    if not args.cpu_rehearsal:
        check(dev["platform"] == "tpu", f"train worker computes on {dev['platform']}")
        check(
            m["mosaic_kernels"] == ["flash_bwd", "flash_fwd"],
            f"train step holds kernels {m['mosaic_kernels']}",
        )
    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(m["retraces"] == 0, f"train step traced again {m['retraces']} times")
    emit(
        "train", dev, event="trained", model="gpt", n_params=m["n_params"],
        batch=batch, seq_len=model["seq_len"], steps=len(losses), losses=losses,
        mosaic_kernels=m["mosaic_kernels"], retraces_after_first_step=m["retraces"],
        jit_cache_size=m["jit_cache_size"],
        state_init_s=m["init_s"], first_step_s=m["first_step_s"],
        memory=m["device_report"]["memory"],
        compile_cache=m["device_report"]["compile_cache"],
    )

    import jax._src.xla_bridge as xb

    check(not xb._backends, f"the driver initialised backends {list(xb._backends)}")


def phase_train(args) -> None:
    import ray_tpu

    ray_tpu.init()
    try:
        _train(args)
    finally:  # pass or fail, the train worker lets go of the chip
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# parent: one child at a time, nothing left behind
# ---------------------------------------------------------------------------


def _descendants() -> list:
    """Live processes re-parented to this one (it is their subreaper)."""
    me, out = os.getpid(), []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as f:
                stat = f.read()
        except OSError:
            continue
        state, ppid = stat.rsplit(")", 1)[1].split()[:2]
        if int(ppid) == me and state != "Z":
            out.append(int(pid))
    return out


def _run_child(phase: str, args, capture: bool = False) -> str:
    """Run one phase in its own process; return its stdout if captured.
    Afterwards NOTHING it started may be alive: orphans re-parent here
    (PR_SET_CHILD_SUBREAPER), get a moment to finish exiting, and are
    killed — a survivor holding the chip would fail the next phase."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--child", "--tp", str(args.tp)]
    if args.cpu_rehearsal:
        cmd.append("--cpu-rehearsal")
    note(f"phase {phase}: start")
    t0 = time.time()
    proc = subprocess.run(
        cmd, cwd=HERE, stdout=subprocess.PIPE if capture else None, text=True,
    )
    deadline = time.time() + 10.0
    while _descendants() and time.time() < deadline:
        try:
            os.waitpid(-1, os.WNOHANG)
        except ChildProcessError:
            pass
        time.sleep(0.2)
    left = _descendants()
    for pid in left:
        os.kill(pid, signal.SIGKILL)
    for pid in left:
        os.waitpid(pid, 0)
    note(
        f"phase {phase}: exit {proc.returncode} after {time.time() - t0:.0f}s"
        + (f"; killed leftover processes {left}" if left else "")
    )
    if proc.returncode != 0:
        raise SystemExit(f"chip_smoke: phase {phase} failed (exit {proc.returncode})")
    return proc.stdout if capture else ""


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phase", choices=("probe",) + PHASES,
                    help="run this phase only (no result line)")
    ap.add_argument("--child", action="store_true",
                    help="internal: this process IS the phase")
    ap.add_argument("--tp", type=int, default=1,
                    help="serve phase: tensor parallelism of the replica")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on a CPU; never prints the result line")
    args = ap.parse_args()
    os.makedirs(OUT_DIR, exist_ok=True)
    if args.child:
        {"probe": phase_probe, "serve": phase_serve, "kernels": phase_kernels,
         "train": phase_train}[args.phase](args)
        return

    PR_SET_CHILD_SUBREAPER = 36
    ctypes.CDLL(None, use_errno=True).prctl(PR_SET_CHILD_SUBREAPER, 1, 0, 0, 0)
    if args.phase:
        _run_child(args.phase, args)
        return
    device = json.loads(_run_child("probe", args, capture=True).splitlines()[-1])
    if args.cpu_rehearsal:
        note(f"CPU REHEARSAL on {device}: control flow only, no result line")
    elif device["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (devices are {device}); this "
            "script proves the system on the chip and has nothing to say here"
        )
    for phase in PHASES:
        _run_child(phase, args)
    check("jax" not in sys.modules, "the parent imported jax")
    if args.cpu_rehearsal:
        note("rehearsal finished; a rehearsal is not a pass")
        raise SystemExit(3)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
