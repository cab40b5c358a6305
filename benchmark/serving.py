"""One serving run, whatever the traffic kind: start the program's own
``serve.run(build_llm_app(...))`` with the configuration's sizes, drive
the kind's plan through ``client.py`` over the HTTP proxy, read the
program's counters at the window's two edges, take the device trace in a
traced run, and check the outputs.

The process that runs this is the ``ray_tpu`` driver.  It never opens a
jax backend: the replica worker is the one process on the chip(s).
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time

from benchmark import harness as H

#: what ``start_trace`` was seen to take on the chip, so that the traced
#: slice still ends with the window
TRACE_START_S = 4.0
#: a cut stream has stalled when it was silent for this many median gaps
STALL_GAPS = 20


def _engine_objects(config: dict, rehearsal: bool):
    """The program's model and engine configurations: the model's through
    the configuration's FAMILY (``families/<family>.py``), the engine's
    fields passed through as the file has them."""
    from ray_tpu.llm import EngineConfig

    sizes = H.sizes(config, rehearsal)
    return (H.family_piece(config, "model_config")(sizes),
            EngineConfig(**sizes["engine"]))


def _build_app(config: dict, model_cfg, engine_cfg, traced: bool):
    """``--trace 0`` goes through ``build_llm_app`` untouched.  The traced
    run binds the subclass that adds the trace hook, with the same
    ``deployment(...)`` options ``build_llm_app`` uses."""
    from ray_tpu.serve.llm import build_llm_app

    dep, model = config["deployment"], H.family_piece(config, "SERVE_MODEL")
    if not traced:
        return build_llm_app(
            model=model, model_cfg=model_cfg, engine_config=engine_cfg,
            seed=dep["weights_seed"],
            max_ongoing_requests=dep["max_ongoing_requests"],
        )
    from ray_tpu.serve.api import deployment

    from benchmark.traced_deployment import TracedLLMDeployment

    return deployment(
        TracedLLMDeployment, name="LLMDeployment", num_replicas=1,
        max_ongoing_requests=dep["max_ongoing_requests"],
        autoscaling_config=None, stream_resume_arg="resume_tokens",
        stream_deadline_arg="deadline_s",
    ).bind(
        model=model, model_cfg=model_cfg, engine_config=engine_cfg,
        seed=dep["weights_seed"], warmup=True,
    )


def _post(port: int, payload: dict) -> list:
    """One request outside the window, whole reply (the probes)."""
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        conn.request(
            "POST", "/llm", body=json.dumps(payload),
            headers={"content-type": "application/json"},
        )
        resp = conn.getresponse()
        if resp.status != 200:
            raise H.BenchFailure(f"probe: HTTP {resp.status} {resp.read()[:300]!r}")
        return [json.loads(line) for line in resp if line.strip()]
    finally:
        conn.close()


def probe_prompts(config: dict, vocab: int, rehearsal: bool) -> list:
    """The probe requests, fixed by the CONFIGURATION (never by ``--seed``):
    greedy, ``out`` tokens each, prompt lengths as listed."""
    import random

    spec = H.sizes(config, rehearsal)["correctness"]
    rng = random.Random(spec["probe_seed"])
    return [
        dict(prompt=[rng.randrange(1, vocab) for _ in range(n)],
             max_tokens=spec["probe_out_tokens"])
        for n in spec["probe_prompt_lens"]
    ]


def _identity_probes(port: int, probes: list, vocab: int) -> dict:
    """(b) of the correctness rule: the same greedy prompt cold and then as
    a prefix hit, and one seeded request twice, token for token.  The cold
    probes go side by side, then the three repeats side by side: what a
    request returns must not depend on its neighbours in the batch."""
    from concurrent.futures import ThreadPoolExecutor

    seeded = dict(probes[1], temperature=0.8, top_p=0.95, top_k=40, seed=1234)
    with ThreadPoolExecutor(max_workers=len(probes)) as pool:
        outs = list(pool.map(lambda p: _post(port, p), probes))
        again, s1, s2 = pool.map(lambda p: _post(port, p), [probes[0], seeded, seeded])
    for p, o in zip(probes, outs):
        H.check(len(o) == p["max_tokens"], f"probe returned {len(o)} tokens")
        H.check(all(isinstance(t, int) and 0 <= t < vocab for t in o),
                "probe token outside the vocabulary")
    return {
        "outs": outs,
        "prefix_hit_identical": again == outs[0],
        "seeded_twice_identical": s1 == s2,
    }


def _reference_verdict(config: dict, probes: list, outs: list, rehearsal: bool) -> dict:
    """(c): teacher-forced check of the probe tokens against the plain
    reference, in a child that opens the chip AFTER the replica let go of
    it.  The verdict is cached by configuration and tokens, so only the
    first run of a cell in a checkout pays for it."""
    import hashlib

    sizes = H.sizes(config, rehearsal)
    key = hashlib.sha256(json.dumps(
        [{k: v for k, v in sizes.items() if k != "rehearsal"}, probes, outs],
        sort_keys=True,
    ).encode()).hexdigest()[:24]
    path = os.path.join(H.CACHE_DIR, "verdicts", f"{config['name']}-{key}.json")
    if os.path.exists(path):
        verdict = H.load_json(path)
        verdict["cached"] = True
        return verdict
    os.makedirs(os.path.dirname(path), exist_ok=True)
    job = path + ".job"
    with open(job, "w") as f:
        json.dump({"config": config, "probes": probes, "outs": outs,
                   "rehearsal": rehearsal}, f)
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, os.path.join(H.BENCH_DIR, "reference_check.py"), job, path],
        cwd=H.ROOT,
    )
    os.remove(job)
    H.check(proc.returncode == 0, f"reference check exited {proc.returncode}")
    verdict = H.load_json(path)
    verdict["cached"] = False
    verdict["seconds"] = round(time.time() - t0, 1)
    return verdict


def _lateness_line(records: list) -> None:
    late = [r["sent"] - r["due"] for r in records if r["sent"] is not None]
    if not late:
        return
    med, worst = H.median(late) * 1e3, max(late) * 1e3
    H.emit(
        "generator_lateness", median_ms=med, max_ms=worst, requests=len(late),
        warning=("GENERATOR RAN LATE: median lateness over 5 ms, the offered "
                 "load was not the planned one") if med > 5.0 else None,
    )


def window_gaps(records: list, window: tuple) -> list:
    """Gaps between successive token lines whose LATER line reached the
    client inside the window, every request pooled."""
    lo, hi = window
    return [b - a for r in records for a, b in zip(r["times"], r["times"][1:])
            if lo <= b < hi]


def _client_summary(records: list, window: tuple, seconds: float) -> None:
    """What the clients saw, on an earlier line of every serving run: the
    knee sweeps read it; no metric does."""
    lo, hi = window
    done = [r for r in records if r["done"] is not None and r["complete"]
            and lo <= r["done"] < hi]
    due = [r for r in records if lo <= r["due"] < hi]
    ttft = [r["times"][0] - r["due"] for r in due if r["times"]]
    gaps = window_gaps(records, window)
    toks = sum(1 for r in records for t in r["times"] if lo <= t < hi)

    def pct(v, p):
        return 1e3 * H.percentile(v, p) if v else None

    H.emit(
        "client_summary", completed_per_s=len(done) / seconds,
        out_tokens_per_s=toks / seconds, due_in_window=len(due),
        due_without_first_token=len(due) - len(ttft),
        completed_in_window=len(done),
        prompt_tokens_completed_per_s=sum(r["prompt_len"] for r in done) / seconds,
        ttft_ms={p: pct(ttft, p) for p in (50, 90, 99)},
        itl_ms={p: pct(gaps, p) for p in (50, 95, 99)}, gaps=len(gaps),
    )


def _structural_failures(attempted: list, vocab: int, end: float,
                         queue_is_load: bool) -> list:
    """(a): what counts under ``failed``.  The run does not wait for every
    stream to end (a 384-token answer takes most of a minute): a request
    still open when the run ends is CUT.  A cut request that has no token
    at all has failed — it was starved — unless the mix is above the knee
    (``queue_is_load``: callers outnumber the engine's slots and waiting is
    the load; starvation shows in tokens/s there).  A cut request that was
    streaming fails only if it had stalled: silent for longer than
    ``STALL_GAPS`` median gaps."""
    gaps = [b - a for r in attempted for a, b in zip(r["times"], r["times"][1:])]
    stall = STALL_GAPS * H.median(gaps) if gaps else float("inf")
    bad = []
    for r in attempted:
        why = None
        if any(not isinstance(t, int) or not 0 <= t < vocab for t in r["tokens"]):
            why = "token outside the vocabulary"
        elif len(r["tokens"]) > r["max_tokens"]:
            why = f"{len(r['tokens'])} tokens, asked {r['max_tokens']}"
        elif r["cut"]:
            if not r["times"]:
                if not queue_is_load:
                    why = (f"no first token {end - r['due']:.1f} s after it was "
                           "due, when the run ended")
            elif end - r["times"][-1] > stall:
                why = f"stream silent for {end - r['times'][-1]:.1f} s when the run ended"
        elif r["status"] != 200:
            why = f"status {r['status']} {r.get('error', '')[:120]}"
        elif r.get("error") or not r["complete"]:
            why = f"broken stream {r.get('error', '')[:120]}"
        elif len(r["tokens"]) != r["max_tokens"]:
            why = f"{len(r['tokens'])} tokens, asked {r['max_tokens']}"
        if why:
            bad.append((r["id"], why))
    return bad


def run_cell(ctx: dict, make_plan) -> dict:
    """Run one serving cell.  ``make_plan(traffic, seed, vocab, seconds)``
    is the traffic kind's generator; it returns the client's plan without
    ``t0``/``port`` plus ``lead_s`` and ``drain_s``."""
    import ray_tpu
    from ray_tpu import serve

    config, traffic, args = ctx["config"], ctx["traffic"], ctx["args"]
    rehearsal, traced = args.rehearsal, bool(args.trace)
    model_cfg, engine_cfg = _engine_objects(config, rehearsal)
    vocab = model_cfg.vocab_size
    rdir = ctx["run_dir"]
    marks = {"process_start": ctx["t_start"]}

    ray_tpu.init()
    try:
        marks["ray_init"] = time.time()
        handle = serve.run(
            _build_app(config, model_cfg, engine_cfg, traced),
            name="llm", http=True, http_port=0,
        )
        marks["replica_ready"] = time.time()
        controller = ray_tpu.get_actor("SERVE_CONTROLLER")
        port = ray_tpu.get(controller.get_proxy_port.remote(), timeout=30)
        # device_report() lowers every step (seconds at this depth): it is
        # called once, after the drain, and never inside set-up
        H.emit("replica_ready",
               serve_run_s=marks["replica_ready"] - marks["ray_init"],
               depth=model_cfg.n_layers, tp=engine_cfg.tp)

        # the identity probes run BEFORE the load, as set-up: after it the
        # engine may still hold a queue of requests whose callers have left
        # (a caller's leaving is noticed at its first token), and the
        # probes would wait behind them for tens of seconds of chip time
        probes = probe_prompts(config, vocab, rehearsal)
        ident = _identity_probes(port, probes, vocab)
        marks["probes"] = time.time()

        plan = make_plan(traffic, args.seed, vocab, args.seconds)
        lead_s, drain_s = plan.pop("lead_s"), plan.pop("drain_s")
        queue_is_load = plan.pop("queue_is_load")
        t0 = time.time() + 1.5  # the client needs a moment to read its plan
        t_open, t_close = t0 + lead_s, t0 + lead_s + args.seconds
        plan.update(t0=t0, port=port, app="llm",
                    hard_stop=lead_s + args.seconds + drain_s)
        if plan["mode"] == "closed":
            plan["stop_new"] = lead_s + args.seconds
        plan_path = os.path.join(rdir, "plan.json")
        rec_path = os.path.join(rdir, "records.json")
        with open(plan_path, "w") as f:
            json.dump(plan, f)
        client = subprocess.Popen(
            [sys.executable, os.path.join(H.BENCH_DIR, "client.py"),
             plan_path, rec_path],
        )
        marks["client_started"] = time.time()

        def stats_at(t: float) -> dict:
            time.sleep(max(0.0, t - time.time()))
            s = handle.stats.remote().result()
            s["_t"] = time.time()
            return s

        counters = {"open": stats_at(t_open)}
        trace_dir = None
        if traced:
            # the LAST seconds of the window: starting the profiler takes
            # seconds and stopping it tens of seconds of the replica's
            # time, and the counters are read across the whole window
            span = min(float(traffic.get("trace_s", 3.0)), args.seconds * 0.25)
            trace_dir = os.path.join(rdir, "trace")
            counters["trace_start"] = stats_at(t_close - span - TRACE_START_S)
            t_a = time.time()
            handle.start_trace.remote(trace_dir).result()
            t_b = time.time()
        counters["close"] = stats_at(t_close)
        if traced:
            counters["trace_stop"] = counters["close"]
            t_c = time.time()
            handle.stop_trace.remote().result()
            H.emit("trace_taken", start_call_s=t_b - t_a, traced_s=t_c - t_b,
                   stop_call_s=time.time() - t_c)
        rc = client.wait(timeout=drain_s + 120)
        H.check(rc == 0, f"the load generator exited {rc}")
        records = H.load_json(rec_path)["records"]
        _lateness_line(records)
        _client_summary(records, (lead_s, lead_s + args.seconds), args.seconds)

        # -- after the drain: counters, audits, memory ---------------------
        counters["end"] = stats_at(time.time())
        audits = handle.audit.remote().result()
        after = handle.device_report.remote().result()
        retraces = counters["end"]["retraces"]  # probes and window included
        device = {
            "platform": after["platform"], "kind": after["device_kind"],
            "count": after["device_count"],
        }
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
        killed = H.reap_descendants()
        if killed:
            H.note(f"killed leftover processes {killed}")

    import jax._src.xla_bridge as xb

    H.check(not xb._backends, f"the driver opened backends {list(xb._backends)}")
    if not rehearsal:
        H.check(device["platform"] == "tpu",
                f"the replica computed on {device['platform']}, not a TPU")
        H.check(device["count"] >= ctx["workload"]["chips"],
                f"the cell needs {ctx['workload']['chips']} chips, jax saw "
                f"{device['count']}")

    # the requests of the window: due before it closed, and not over before
    # it opened (the lead-in's requests still streaming or waiting count)
    t_lo, t_hi = lead_s, lead_s + args.seconds
    attempted = [
        r for r in records
        if r["sent"] is not None and r["due"] < t_hi
        and (r["done"] is None or r["done"] >= t_lo)
    ]
    bad = _structural_failures(attempted, vocab, t_hi + drain_s, queue_is_load)
    verdict = _reference_verdict(config, probes, ident["outs"], rehearsal)
    correctness = {
        "structural_failures": len(bad),
        "prefix_hit_identical": ident["prefix_hit_identical"],
        "seeded_twice_identical": ident["seeded_twice_identical"],
        "retraces": retraces,
        "pool_audit_ok": bool(audits["pool"]["ok"]),
        "prefix_audit_ok": audits["prefix_cache"] is None
        or bool(audits["prefix_cache"]["ok"]),
        "reference_ok": bool(verdict["ok"]),
        "reference": {k: verdict.get(k) for k in
                      ("max_deficit", "tolerance", "positions", "cached", "seconds")},
        "jit_cache_sizes": {k: v["cache_size"] for k, v in after["jit_sites"].items()},
    }
    H.emit("correctness", **correctness, first_failures=bad[:5])
    H.emit("engine_counters", **{
        at: {k: c[k] for k in ("_t", "running", "waiting", "kv_utilization", "free_blocks",
                               "steps", "tokens_generated", "prefill_tokens_computed",
                               "preemptions")}
        | {"hit_tokens": c.get("prefix_cache", {}).get("hit_tokens"),
           "evicted_blocks": c.get("prefix_cache", {}).get("evicted_blocks")}
        for at, c in counters.items()
    })
    correct = (
        correctness["prefix_hit_identical"] and correctness["seeded_twice_identical"]
        and retraces == 0 and correctness["pool_audit_ok"]
        and correctness["prefix_audit_ok"] and correctness["reference_ok"]
        and all(n == 1 for n in correctness["jit_cache_sizes"].values())
    )
    H.emit(
        "setup_breakdown",
        ray_init_s=marks["ray_init"] - marks["process_start"],
        serve_run_s=marks["replica_ready"] - marks["ray_init"],
        probes_s=marks["probes"] - marks["replica_ready"],
        plan_s=marks["client_started"] - marks["probes"],
        lead_in_s=t_open - marks["client_started"],
        step_first_call_s=after["first_call_s"],
        compile_cache=after["compile_cache"], versions=after["versions"],
        hbm={k: v for k, v in after["hbm"].items() if k != "per_device"},
    )
    peak = max(
        m.get("peak_bytes_in_use", 0) + m.get("peak_bytes_reserved", 0)
        for m in after["memory"].values()
    ) if after["memory"] else 0
    return {
        "kind": "serving",
        "correct": bool(correct),
        "attempted": len(attempted),
        "failed": len(bad),
        "device": dict(device, memory_peak_bytes=int(peak)),
        "setup_s": t_open - marks["process_start"],
        "window": (lead_s, lead_s + args.seconds),
        "seconds": float(args.seconds),
        "records": records,
        "attempted_records": attempted,
        "counters": counters,
        "trace_dir": trace_dir,
        "device_report": after,
        "model": dataclasses.asdict(model_cfg),
        "engine": dataclasses.asdict(engine_cfg),
        "config": config,
        "traffic": traffic,
    }
