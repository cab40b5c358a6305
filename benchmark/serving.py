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

#: a gap this many times a decode alone carries a prefill chunk too
CHUNK_GAP_FACTOR = 1.25
#: engine steps that give every per-layer reader its samples: the traced
#: slice holds no more of them, however fast the engine steps (PR 27's
#: slices held 119-266 decodes), so a faster engine writes the same trace
TRACE_STEPS = 150
#: where in the window a traced run asks for the counters it takes the
#: engine's steps a second from, and where its slice begins, as shares of
#: the window: ``stop_trace`` then has the rest of the window and the drain
RATE_READ_AT = 0.15
TRACE_AT = 0.3
#: the slice begins up to this long after ``TRACE_AT``, where the plan has
#: most requests due in the ``TRACE_WAIT_S`` before it (``slice_begin``)
TRACE_REACH_S = 5.0
TRACE_WAIT_S = 3.0
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
    run binds the subclass whose ``stop_trace`` writes the ``.xplane.pb``
    alone (``traced_deployment.py``), with the same ``deployment(...)``
    options ``build_llm_app`` uses."""
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


class ReferenceJob:
    """(c): teacher-forced check of the probe tokens against the plain
    reference, in a child that opens the chip AFTER the replica let go of
    it.  The child is started here and joined by ``verdict()``: it needs
    the chip and the trace's reduction needs the host, so the two run side
    by side.  The verdict is cached by configuration and tokens, so only
    the first run of a cell in a checkout pays for it."""

    def __init__(self, config: dict, probes: list, outs: list, rehearsal: bool):
        import hashlib

        sizes = H.sizes(config, rehearsal)
        key = hashlib.sha256(json.dumps(
            [{k: v for k, v in sizes.items() if k != "rehearsal"}, probes, outs],
            sort_keys=True,
        ).encode()).hexdigest()[:24]
        self.path = os.path.join(H.CACHE_DIR, "verdicts", f"{config['name']}-{key}.json")
        self.proc, self.t0 = None, time.time()
        if os.path.exists(self.path):
            return
        os.makedirs(os.path.dirname(self.path), exist_ok=True)
        self.job = self.path + ".job"
        with open(self.job, "w") as f:
            json.dump({"config": config, "probes": probes, "outs": outs,
                       "rehearsal": rehearsal}, f)
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(H.BENCH_DIR, "reference_check.py"),
             self.job, self.path],
            cwd=H.ROOT,
        )

    def verdict(self) -> dict:
        if self.proc is None:
            return dict(H.load_json(self.path), cached=True, seconds=0.0)
        rc = self.proc.wait()
        os.remove(self.job)
        H.check(rc == 0, f"reference check exited {rc}")
        return dict(H.load_json(self.path), cached=False,
                    seconds=time.time() - self.t0)


def slice_seconds(traffic: dict, seconds: float, steps_per_s) -> float:
    """How long the traced slice is planned to be: what the traffic file
    asks (``trace_s``, at most a quarter of the window), and no longer
    than ``TRACE_STEPS`` engine steps take at the rate the window has
    shown; without a rate, what the file asks."""
    span = min(float(traffic.get("trace_s", 3.0)), seconds * 0.25)
    if steps_per_s and steps_per_s > 0:
        span = min(span, TRACE_STEPS / steps_per_s)
    return span


def chunk_step_share(counters: dict, prefill_chunk: int):
    """At least this share (%) of the window's engine steps carried a
    prefill chunk: the prompt tokens computed across the window over the
    chunk's size (a prompt's last chunk is not full, so a little more did),
    over the steps.  The engine's own count beside the clients'
    ``chunk_gap_share``."""
    steps = counters["close"]["steps"] - counters["open"]["steps"]
    tokens = (counters["close"]["prefill_tokens_computed"]
              - counters["open"]["prefill_tokens_computed"])
    return 100.0 * tokens / prefill_chunk / steps if steps > 0 else None


def slice_begin(dues: list, nominal: float, span_s: float) -> float:
    """Where the traced slice begins, in the plan's seconds: within
    ``TRACE_REACH_S`` after ``nominal``, the instant with most requests due
    from ``TRACE_WAIT_S`` before it to a second before the slice ends.  A
    request's prefill follows its due instant by the wait for the engine's
    lock (seconds), and a slice that holds no prefill chunk gives the
    chunk's reader nothing to read: at 0.8 requests/s three seconds in
    twelve hold none.  The plan alone decides, so the same seed traces the
    same slice on every tree; a plan without due instants (a closed loop
    sends back to back) begins at ``nominal``."""
    best, best_n = nominal, -1
    for i in range(int(TRACE_REACH_S / 0.25) + 1):
        b = nominal + 0.25 * i
        n = sum(1 for d in dues if b - TRACE_WAIT_S <= d < b + span_s - 1.0)
        if n > best_n:
            best, best_n = b, n
    return best


def steps_per_second(first: dict, later: dict):
    """Engine steps a second between two ``stats()`` readings, over the
    instants the engine took them at (``t_read``); None where they lie
    under a second apart (the first one waited for the lock that long)."""
    dt = later.get("t_read", 0.0) - first.get("t_read", 0.0)
    return (later["steps"] - first["steps"]) / dt if dt >= 1.0 else None


def take_slice(handle, trace_dir: str, t_begin: float, span_s: float,
               clock=time.time, sleep=time.sleep):
    """The traced slice: start the profiler at ``t_begin`` and ask it to
    stop ``span_s`` after it has started; nothing else happens in between.
    ``stats()`` waits for the engine's lock, for seconds under load, and
    ``stop_trace`` writes out every traced step at tens of times its
    length: so the readings at the slice's two ends are ASKED for here,
    ``trace_start`` just before ``start_trace`` and ``trace_stop`` just
    before ``stop_trace`` (each outside the slice; ``t_read`` in each says
    when the engine answered), and ``stop_trace`` is asked for and left to
    run.  Returns ``join``: call it once the window and the drain are
    over; it waits for the profiler and gives (the two readings, the
    slice's timing)."""
    import threading

    sleep(max(0.0, t_begin - clock()))
    before = handle.stats.remote()
    t_a = clock()
    handle.start_trace.remote(trace_dir).result()
    t_b = clock()
    sleep(max(0.0, t_b + span_s - clock()))
    after = handle.stats.remote()
    t_c = clock()
    stop = handle.stop_trace.remote()
    stopped = []

    def wait_for_stop():
        try:
            stop.result()
            stopped.append((clock(), None))
        except BaseException as e:  # raised again by join()
            stopped.append((clock(), e))

    waiter = threading.Thread(target=wait_for_stop, daemon=True)
    waiter.start()

    def join() -> tuple:
        t_j = clock()
        waiter.join()
        t_d, error = stopped[0]
        if error is not None:
            raise error
        readings = {name: dict(ref.result(), _t=clock())
                    for name, ref in (("trace_start", before), ("trace_stop", after))}
        timing = {"planned_s": span_s, "start_call_s": t_b - t_a, "traced_s": t_c - t_b,
                  "stop_call_s": t_d - t_c, "waited_for_stop_s": clock() - t_j}
        return readings, timing

    return join


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

    # a gap that carries a prefill chunk beside the decode stands clear of
    # a decode alone, taken as the gaps' fastest tenth (the median itself
    # carries a chunk once most gaps do; two lines that one read delivered
    # are no gap and stay out of the tenth).  A chunk adds 70% to a decode
    # on one chip and a third under tp=4, a long row a fifth at most: the
    # line is drawn a quarter above.  Where this share nears 5% the 95th
    # percentile flips between the two (README, "Re-rating a chat cell");
    # the engine's own count stands on the ``engine_counters`` line
    apart = [g for g in gaps if g >= 1e-3]
    alone = H.percentile(apart, 10) if apart else None
    long_gaps = sum(1 for g in gaps if g > CHUNK_GAP_FACTOR * alone) if apart else 0
    H.emit(
        "client_summary", completed_per_s=len(done) / seconds,
        out_tokens_per_s=toks / seconds, due_in_window=len(due),
        due_without_first_token=len(due) - len(ttft),
        completed_in_window=len(done),
        prompt_tokens_completed_per_s=sum(r["prompt_len"] for r in done) / seconds,
        ttft_ms={p: pct(ttft, p) for p in (50, 90, 99)},
        itl_ms={p: pct(gaps, p) for p in (50, 90, 93, 95, 97, 99)}, gaps=len(gaps),
        chunk_gap_share=100.0 * long_gaps / len(gaps) if gaps else None,
        decode_alone_gap_ms=1e3 * alone if apart else None,
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
    ``t0``/``port`` plus ``lead_s`` and ``drain_s``.  The run that comes
    back is whole but for ``correct``: the reference's child is still on
    the chip, and ``run["finish"]()`` joins it and gives the verdict, so
    that the caller reduces the trace meanwhile."""
    import ray_tpu
    from ray_tpu import serve

    config, traffic, args = ctx["config"], ctx["traffic"], ctx["args"]
    rehearsal, traced = args.rehearsal, bool(args.trace)
    model_cfg, engine_cfg = _engine_objects(config, rehearsal)
    vocab = model_cfg.vocab_size
    rdir, budget = ctx["run_dir"], ctx["budget"]
    marks = {"process_start": ctx["t_start"]}

    ray_tpu.init()
    try:
        marks["ray_init"] = budget.mark("ray_init")
        handle = serve.run(
            _build_app(config, model_cfg, engine_cfg, traced),
            name="llm", http=True, http_port=0,
        )
        marks["replica_ready"] = budget.mark("serve_run")
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
        marks["probes"] = budget.mark("probes")

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
        marks["client_started"] = budget.mark("plan")

        def stats_at(t: float) -> dict:
            time.sleep(max(0.0, t - time.time()))
            s = handle.stats.remote().result()
            s["_t"] = time.time()
            return s

        counters = {"open": stats_at(t_open)}
        budget.mark("lead_in", t_open)
        trace_dir = join_slice = None
        if traced:
            # a slice in the window's first half: what stop_trace writes out
            # it then writes under the rest of the window and the drain.
            # A traced run alone asks for one more reading before it, and
            # takes the engine's steps a second from it, so that the slice
            # is bounded in work as well as in seconds; that reading is
            # never waited for past the instant the slice is planned at
            time.sleep(max(0.0, t_open + RATE_READ_AT * args.seconds - time.time()))
            rate_ref = handle.stats.remote()
            span = slice_seconds(traffic, args.seconds, None)
            dues = [r["due"] for r in plan.get("requests", [])]
            t_begin = t0 + slice_begin(dues, lead_s + TRACE_AT * args.seconds, span)
            try:
                counters["rate_read"] = rate_ref.result(
                    timeout=max(0.1, t_begin - 1.0 - time.time()))
                rate = steps_per_second(counters["open"], counters["rate_read"])
            except TimeoutError:
                rate = None
            span = slice_seconds(traffic, args.seconds, rate)
            trace_dir = os.path.join(rdir, "trace")
            join_slice = take_slice(handle, trace_dir, t_begin, span)
        counters["close"] = stats_at(t_close)
        budget.mark("window", t_close)
        budget.mark("close_read")
        rc = client.wait(timeout=drain_s + 120)
        H.check(rc == 0, f"the load generator exited {rc}")
        budget.mark("drain_rest")
        if traced:
            readings, timing = join_slice()
            counters.update(readings)
            budget.mark("stop_trace_rest")
            H.emit("trace_taken", steps_per_s=rate, **timing,
                   steps=readings["trace_stop"]["steps"] - readings["trace_start"]["steps"],
                   reads_apart_s=readings["trace_stop"]["t_read"]
                   - readings["trace_start"]["t_read"])
        records = H.load_json(rec_path)["records"]
        _lateness_line(records)
        _client_summary(records, (lead_s, lead_s + args.seconds), args.seconds)
        budget.mark("client_records")

        # -- after the drain: counters, audits, memory ---------------------
        counters["end"] = stats_at(time.time())
        audits = handle.audit.remote().result()
        after = handle.device_report.remote().result()
        retraces = counters["end"]["retraces"]  # probes and window included
        device = {
            "platform": after["platform"], "kind": after["device_kind"],
            "count": after["device_count"],
        }
        budget.mark("after_drain_reads")
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
        killed = H.reap_descendants()
        if killed:
            H.note(f"killed leftover processes {killed}")
        budget.mark("shutdown")

    import jax._src.xla_bridge as xb

    H.check(not xb._backends, f"the driver opened backends {list(xb._backends)}")
    if not rehearsal:
        H.check(device["platform"] == "tpu",
                f"the replica computed on {device['platform']}, not a TPU")
        H.check(device["count"] >= ctx["workload"]["chips"],
                f"the cell needs {ctx['workload']['chips']} chips, jax saw "
                f"{device['count']}")
    # once the chip is free the reference takes it, and this process goes on
    waited_s, holders = H.wait_for_free_chips()
    H.emit("chips_free", waited_s=waited_s, holders=holders)
    budget.mark("chips_free")
    reference = ReferenceJob(config, probes, ident["outs"], rehearsal)

    # the requests of the window: due before it closed, and not over before
    # it opened (the lead-in's requests still streaming or waiting count)
    t_lo, t_hi = lead_s, lead_s + args.seconds
    attempted = [
        r for r in records
        if r["sent"] is not None and r["due"] < t_hi
        and (r["done"] is None or r["done"] >= t_lo)
    ]
    bad = _structural_failures(attempted, vocab, t_hi + drain_s, queue_is_load)
    H.emit("engine_counters", **{
        at: {k: c.get(k) for k in ("_t", "t_read", "running", "waiting", "kv_utilization",
                                   "free_blocks", "steps", "tokens_generated",
                                   "prefill_tokens_computed", "preemptions", "sampler")}
        | {"hit_tokens": c.get("prefix_cache", {}).get("hit_tokens"),
           "evicted_blocks": c.get("prefix_cache", {}).get("evicted_blocks")}
        for at, c in counters.items()
    }, chunk_step_share=chunk_step_share(counters, engine_cfg.prefill_chunk))
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

    def finish() -> tuple:
        """Join the reference and decide ``correct`` (returned after what
        the reference took and whether it was cached); every number compared
        goes beside its limit on the ``correctness`` line, and on standard
        error as the run's last lines."""
        verdict = reference.verdict()
        budget.mark("reference_join")
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
        H.note("correctness " + json.dumps(dict(correctness, first_failures=bad[:5])))
        return correctness["reference"], bool(
            correctness["prefix_hit_identical"] and correctness["seeded_twice_identical"]
            and retraces == 0 and correctness["pool_audit_ok"]
            and correctness["prefix_audit_ok"] and correctness["reference_ok"]
            and all(n == 1 for n in correctness["jit_cache_sizes"].values())
        )

    peak = max(
        m.get("peak_bytes_in_use", 0) + m.get("peak_bytes_reserved", 0)
        for m in after["memory"].values()
    ) if after["memory"] else 0
    return {
        "kind": "serving",
        "finish": finish,
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
