"""Core runtime microbenchmarks, named after the reference's harness.

Reference: ``python/ray/_private/ray_perf.py:93-315`` — the nightly
microbenchmark suite whose metric names (single-client tasks sync/async,
1:1 / 1:n actor calls, put/get throughput, ``ray.wait``) BASELINE.md asks
this build to reproduce. Prints one JSON line per metric plus a combined
line; ``python bench_core.py`` runs everything on a local cluster.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np


def bench_environment() -> dict:
    """Record the conditions the benchmark ran under.

    Round 4's core numbers collapsed ~5x purely from VM contention and
    nothing in the output could tell that apart from a regression
    (VERDICT r4 weakness #2).  Three signals fix that:

    - ``cpu_count``: 1-core boxes serialize the head/worker/driver trio.
    - ``loadavg``: load already on the box when we started.
    - ``spin_canary_mops``: a fixed pure-Python spin loop measured twice
      (before/after could also drift); on an uncontended box this is a
      property of the interpreter + CPU only, so a low value directly
      measures how much CPU the bench process actually received.
    """
    def spin_mops() -> float:
        n = 2_000_000
        t0 = time.perf_counter()
        x = 0
        for i in range(n):
            x += i
        dt = time.perf_counter() - t0
        return round(n / dt / 1e6, 2)

    try:
        load = tuple(round(v, 2) for v in os.getloadavg())
    except OSError:  # pragma: no cover - non-unix
        load = None
    return {
        "cpu_count": os.cpu_count(),
        "loadavg_1_5_15": load,
        "spin_canary_mops": spin_mops(),
    }


def timeit(name: str, fn, unit: str = "per_s", warmup=True, windows: int = 3,
           extra: dict = None) -> dict:
    """Median of three measurement windows (like bench.py's TPU metric):
    single short windows on a shared VM swing ±40% with scheduler noise,
    which round 3 initially misread as regressions.  ``extra`` merges
    qualifier tags into the printed record (e.g. ``loopback: true``)."""
    if warmup:
        fn()
    rates = []
    for _ in range(max(windows, 1)):
        t0 = time.perf_counter()
        n = fn()
        rates.append(n / (time.perf_counter() - t0))
    rec = {"metric": name, "value": round(sorted(rates)[len(rates) // 2], 2), "unit": unit}
    if extra:
        rec.update(extra)
    print(json.dumps(rec), flush=True)
    return rec


def main() -> list[dict]:
    import ray_tpu

    env = bench_environment()
    print(json.dumps({"metric": "bench_environment", **env}), flush=True)

    ray_tpu.init(num_cpus=8)
    results = []

    # -- tasks (ray_perf: "single client tasks sync/async") ----------------
    @ray_tpu.remote
    def noop():
        return None

    def tasks_sync(n=600):
        for _ in range(n):
            ray_tpu.get(noop.remote())
        return n

    def tasks_async(n=3000):
        ray_tpu.get([noop.remote() for _ in range(n)])
        return n

    results.append(timeit("single_client_tasks_sync", tasks_sync))
    results.append(timeit("single_client_tasks_async", tasks_async))

    # -- actor calls (ray_perf: "1:1 actor calls sync/async", "1:n") -------
    @ray_tpu.remote
    class A:
        def noop(self):
            return None

    a = A.remote()
    ray_tpu.get(a.noop.remote())

    def actor_sync(n=600):
        for _ in range(n):
            ray_tpu.get(a.noop.remote())
        return n

    def actor_async(n=3000):
        ray_tpu.get([a.noop.remote() for _ in range(n)])
        return n

    results.append(timeit("single_client_actor_calls_sync", actor_sync))
    results.append(timeit("single_client_actor_calls_async", actor_async))

    actors = [A.remote() for _ in range(4)]
    ray_tpu.get([x.noop.remote() for x in actors])

    def actor_one_to_n(n=250):
        ray_tpu.get([x.noop.remote() for x in actors for _ in range(n)])
        return n * len(actors)

    results.append(timeit("client_1_to_4_actor_calls_async", actor_one_to_n))

    # -- object plane (ray_perf: put/get GB/s) -----------------------------
    small = np.zeros(1024, np.uint8)

    def put_small(n=500):
        for _ in range(n):
            ray_tpu.put(small)
        return n

    results.append(timeit("single_client_put_calls_1kb", put_small))

    big = np.zeros(10 * 1024 * 1024, np.uint8)  # 10 MB

    def put_gigabytes(n=20):
        refs = [ray_tpu.put(big) for _ in range(n)]
        ray_tpu.get(refs[-1])
        return n * big.nbytes / 1e9

    results.append(timeit("single_client_put_gigabytes", put_gigabytes, unit="GB_per_s"))

    refs_big = [ray_tpu.put(big) for _ in range(8)]

    def get_gigabytes(n=40):
        total = 0
        for i in range(n):
            out = ray_tpu.get(refs_big[i % len(refs_big)])
            total += int(out[::65536].sum())  # touch pages: measure real reads
        return n * big.nbytes / 1e9

    results.append(timeit("single_client_get_gigabytes", get_gigabytes, unit="GB_per_s"))

    # -- wait (ray_perf: "1:1 ray.wait on 1k refs") ------------------------
    refs_1k = [noop.remote() for _ in range(1000)]
    ray_tpu.get(refs_1k)

    def wait_1k(n=100):
        for _ in range(n):
            ray_tpu.wait(refs_1k, num_returns=1000, timeout=10)
        return n

    results.append(timeit("single_client_wait_1k_refs", wait_1k))

    # -- scalability envelope (reference release/benchmarks/README.md:
    # queued tasks, actor fan-out, large-object broadcast) ------------------
    def queued_100k(n=100_000):
        ray_tpu.get([noop.remote() for _ in range(n)], timeout=600)
        return n

    results.append(timeit("envelope_queued_tasks_100k", queued_100k,
                          warmup=False, windows=1))

    @ray_tpu.remote(num_cpus=0)
    class E:
        def ping(self):
            return 1

    def actor_wave(n=200):
        wave = [E.remote() for _ in range(n)]
        assert ray_tpu.get([x.ping.remote() for x in wave], timeout=600) == [1] * n
        for x in wave:
            ray_tpu.kill(x)
        return n

    results.append(timeit("envelope_actors_spawned", actor_wave,
                          warmup=False, windows=1))

    def broadcast_256mb(n=8):
        blob_ref = ray_tpu.put(np.ones((256 << 20) // 8, np.float64))

        @ray_tpu.remote
        def read(b):
            return b.nbytes

        sizes = ray_tpu.get([read.remote(blob_ref) for _ in range(n)], timeout=300)
        return sum(sizes) / 1e9  # logical GB fanned out

    results.append(timeit("envelope_broadcast_256mb_x8", broadcast_256mb,
                          unit="GB_per_s", warmup=False, windows=1))

    ray_tpu.shutdown()
    env["spin_canary_mops_after"] = bench_environment()["spin_canary_mops"]
    print(
        json.dumps(
            {
                "metric": "core_microbench",
                "value": len(results),
                "unit": "metrics",
                "env": env,
                "detail": {r["metric"]: [r["value"], r["unit"]] for r in results},
            }
        ),
        flush=True,
    )
    return results


def obs_ab_main() -> dict:
    """Core-plane observability A/B probe (``--obs-ab``): the
    task-submission + object-plane microbenchmarks most implicated in the
    round-4 4-8x core collapse, run ONCE under whatever
    ``RAY_TPU_EVENTS`` / ``RAY_TPU_METRICS_SERIES`` the caller exported.
    ``bench.py`` invokes this twice — obs ON and obs OFF — in separate
    subprocesses (both knobs are read at import) and emits both numbers
    in the round JSON, so the recorder/series share of any core
    regression is attributable from the bench record alone, before the
    dedicated perf PR profiles the hot path."""
    import ray_tpu
    from ray_tpu._private import events as _events

    env = bench_environment()

    ray_tpu.init(num_cpus=8)

    @ray_tpu.remote
    def noop():
        return None

    def tasks_sync(n=600):
        for _ in range(n):
            ray_tpu.get(noop.remote())
        return n

    def tasks_async(n=3000):
        ray_tpu.get([noop.remote() for _ in range(n)])
        return n

    small = np.zeros(1024, np.uint8)

    def put_small(n=500):
        for _ in range(n):
            ray_tpu.put(small)
        return n

    results = [
        timeit("obs_ab_tasks_sync", tasks_sync),
        timeit("obs_ab_tasks_async", tasks_async),
        timeit("obs_ab_put_calls_1kb", put_small),
    ]
    ray_tpu.shutdown()
    rec = {
        "metric": "core_obs_ab",
        "events_enabled": _events.enabled(),
        "series_enabled": os.environ.get("RAY_TPU_METRICS_SERIES", "1")
        not in ("0", "false", "off"),
        "trace_sample": os.environ.get("RAY_TPU_TRACE_SAMPLE", "1"),
        "env": env,
        "detail": {r["metric"]: r["value"] for r in results},
    }
    print(json.dumps(rec), flush=True)
    return rec


def batched_main() -> dict:
    """Batched-path probe (``--batched``): tasks/s on the pipelined
    submit/reply plane (ISSUE 14) plus the achieved batch sizes and
    per-hop waterfall percentiles, in ONE JSON record (the last stdout
    line). The CI waterfall-probe job uploads it next to the
    core-obs-ab artifact so the IPC trajectory — hop microseconds AND
    how much batching the plane actually achieves — is recorded per PR.
    ``batched_tasks_nested_async`` fans out from a WORKER, which is the
    path that rides submit_batch windows; driver-side async bursts ride
    coalesced dispatch + reply batches."""
    import ray_tpu
    from ray_tpu.util import metrics as um

    env = bench_environment()

    ray_tpu.init(num_cpus=8)

    @ray_tpu.remote
    def noop():
        return None

    @ray_tpu.remote
    def fan(n):
        ray_tpu.get([noop.remote() for _ in range(n)])
        return n

    def tasks_sync(n=600):
        for _ in range(n):
            ray_tpu.get(noop.remote())
        return n

    def tasks_async(n=3000):
        ray_tpu.get([noop.remote() for _ in range(n)])
        return n

    def tasks_nested_async(n=2000):
        return ray_tpu.get(fan.remote(n))

    results = [
        timeit("batched_tasks_sync", tasks_sync),
        timeit("batched_tasks_async", tasks_async),
        timeit("batched_tasks_nested_async", tasks_nested_async),
    ]

    def hist(name: str):
        for v in um.histogram_percentiles(name).get(name, {}).values():
            return {"p50": v.get("p50"), "p99": v.get("p99"), "count": v.get("count")}
        return None

    # per-hop legs from a small TRACED burst, computed from the recent-
    # record ring so ONLY the probe's records count (the head's leg
    # histograms are process-lifetime and the nested arm's worker-side
    # roots sample; the throughput arms themselves stay rootless so
    # stamps never perturb the tasks/s numbers)
    from ray_tpu._private.runtime import get_ctx
    from ray_tpu.util import tracing

    with tracing.trace_context():
        for _ in range(250):
            ray_tpu.get(noop.remote())
    recent = get_ctx().call("waterfall", recent=250).get("recent", [])[-250:]

    def leg_pcts(recs: list) -> dict:
        out = {}
        legs = {k for r in recs for k in r.get("legs", {})}
        for leg in sorted(legs):
            vals = sorted(r["legs"][leg] for r in recs if leg in r.get("legs", {}))
            if vals:
                out[leg] = {
                    "p50": vals[len(vals) // 2],
                    "p99": vals[min(len(vals) - 1, int(len(vals) * 0.99))],
                    "count": len(vals),
                }
        return out

    # read metrics BEFORE shutdown: the registry dies with the cluster
    batch_hists = {
        "core_submit_batch_size": hist("core_submit_batch_size"),
        "core_reply_batch_size": hist("core_reply_batch_size"),
    }
    ray_tpu.shutdown()
    env["spin_canary_mops_after"] = bench_environment()["spin_canary_mops"]
    rec = {
        "metric": "core_batched_path",
        "env": env,
        "detail": {r["metric"]: r["value"] for r in results},
        "batch_hists": batch_hists,
        "waterfall_legs": leg_pcts(recent),
    }
    print(json.dumps(rec), flush=True)
    return rec


def data_plane_main() -> dict:
    """Data-plane probe (``--data-plane``): put/get MB/s at 1KB/64KB/1MB
    across a LOCAL arm (driver + same-machine workers: the ISSUE 18
    shm-locator path) and a REMOTE arm (loopback NodeAgents with
    RAY_TPU_FORCE_DATA_PLANE=1: the peer-to-peer TCP fetch path), plus
    the locality-scheduler placement fraction and a tasks_async canary.
    One JSON record as the last stdout line (the data-plane.json CI
    artifact). ``local_worker_put_*`` is the arm ISSUE 18 targets: puts
    originate in a WORKER process, so before the shm plane every value
    in the (8KB, 100KB] band rode the control socket inline — twice.
    Set RAY_TPU_CORE_SHM_INLINE_THRESHOLD=102400 and
    RAY_TPU_CORE_PUT_PIPELINE=0 to restore that path on the same box
    (the BENCH_r09 paired "before" arm)."""
    import tempfile

    import ray_tpu

    env = bench_environment()
    env["core_shm_inline_threshold"] = int(
        os.environ.get("RAY_TPU_CORE_SHM_INLINE_THRESHOLD", 8 * 1024)
    )
    env["core_put_pipeline"] = os.environ.get(
        "RAY_TPU_CORE_PUT_PIPELINE", "1"
    ).lower() not in ("0", "false", "no")
    sizes = {"1kb": 1024, "64kb": 64 * 1024, "1mb": 1024 * 1024}
    results = []

    # ---- local arm: single-machine cluster, same-node shm path -----------
    ray_tpu.init(num_cpus=4)

    @ray_tpu.remote
    def wput(n, nb):
        b = np.ones(nb, np.uint8)
        for _ in range(n):
            ray_tpu.put(b)
        return n

    @ray_tpu.remote
    def noop():
        return None

    for name, nb in sizes.items():
        blob = np.ones(nb, np.uint8)
        # 32MB per window: sub-20ms windows on a contended 1-core box swing
        # 2x with scheduler noise and drown the arm-vs-arm signal
        reps = max(8, min(512, (32 << 20) // nb))

        def put_burst(n=reps, b=blob):
            for _ in range(n):
                ray_tpu.put(b)
            return n * b.nbytes / 1e6

        results.append(timeit(f"local_driver_put_{name}", put_burst, unit="MB_per_s"))

        pool = [ray_tpu.put(blob) for _ in range(8)]

        def get_burst(n=reps, pool=pool, nb=nb):
            t = 0
            for i in range(n):
                t += int(ray_tpu.get(pool[i % len(pool)])[::4096].sum())
            assert t
            return n * nb / 1e6

        results.append(timeit(f"local_driver_get_{name}", get_burst, unit="MB_per_s"))

        def worker_put(n=reps, nb=nb):
            ray_tpu.get(wput.remote(n, nb), timeout=120)
            return n * nb / 1e6

        results.append(timeit(f"local_worker_put_{name}", worker_put, unit="MB_per_s"))

    # regression canary: the locality pass must not tax argless dispatch
    def tasks_async(n=2000):
        ray_tpu.get([noop.remote() for _ in range(n)])
        return n

    results.append(timeit("tasks_async_canary", tasks_async))
    ray_tpu.shutdown()

    # ---- remote arm: loopback agents, forced peer-to-peer TCP fetch ------
    from ray_tpu._private.config import resolve_authkey
    from ray_tpu._private.head import Head
    from ray_tpu._private.node_agent import NodeAgent

    prev_force = os.environ.get("RAY_TPU_FORCE_DATA_PLANE")
    os.environ["RAY_TPU_FORCE_DATA_PLANE"] = "1"
    authkey = resolve_authkey()
    session = tempfile.mkdtemp(prefix="ray_tpu_bench_dp_")
    head = Head(os.path.join(session, "head.sock"), authkey=authkey)
    head.start()
    host, port = head.listen_tcp("127.0.0.1", 0)
    head.add_node({"CPU": 0.0})
    addr = f"{host}:{port}"
    a = NodeAgent(addr, authkey, resources={"CPU": 2.0, "nodeA": 10.0}).start()
    b = NodeAgent(addr, authkey, resources={"CPU": 2.0, "nodeB": 10.0}).start()
    locality = None
    loc_hits = loc_total = 0
    try:
        ray_tpu.init(address=addr)

        @ray_tpu.remote(resources={"nodeA": 0.01})
        def produce(nb):
            return np.ones(nb, np.uint8)

        @ray_tpu.remote(num_cpus=1)
        def where(x):
            return ray_tpu.get_runtime_context().get_node_id()

        for name, nb in sizes.items():
            reps = max(8, min(64, (16 << 20) // nb))
            pool = [produce.remote(nb) for _ in range(4)]
            ray_tpu.wait(pool, num_returns=len(pool), timeout=60)

            # forced-dp fetches are NOT reader-cached: every get below is a
            # full TCP fetch from nodeA's data server, so pool reuse is fair
            def remote_get(n=reps, pool=pool, nb=nb):
                t = 0
                for i in range(n):
                    t += int(ray_tpu.get(pool[i % len(pool)], timeout=60)[::4096].sum())
                assert t
                return n * nb / 1e6

            # loopback, not a network benchmark: both "remote" agents live
            # on this host, so remote_get MB/s measures the TCP data-plane
            # software path (chunking, recv_bytes_into, dispatch) with no
            # NIC in the loop — compare arms against each other, never
            # against real cross-host bandwidth
            results.append(timeit(
                f"remote_get_{name}", remote_get, unit="MB_per_s",
                extra={
                    "loopback": True,
                    "note": "agents share the bench host; software-path "
                            "MB/s, not network bandwidth",
                },
            ))

        # locality fraction: unconstrained single-arg consumers should land
        # on the node already holding the bytes (acceptance bar: >= 0.9)
        data = produce.remote(64 * 1024)
        ray_tpu.wait([data], timeout=60)
        placed = [ray_tpu.get(where.remote(data), timeout=60) for _ in range(20)]
        locality = placed.count(a.node_id_bin.hex()) / len(placed)
        with head.lock:
            loc_hits, loc_total = head._loc_hits, head._loc_total
    finally:
        if prev_force is None:
            os.environ.pop("RAY_TPU_FORCE_DATA_PLANE", None)
        else:
            os.environ["RAY_TPU_FORCE_DATA_PLANE"] = prev_force
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        a.shutdown()
        b.shutdown()
        head.shutdown()

    env["spin_canary_mops_after"] = bench_environment()["spin_canary_mops"]
    rec = {
        "metric": "core_data_plane",
        "value": len(results),
        "unit": "metrics",
        "env": env,
        "detail": {r["metric"]: r["value"] for r in results},
        "locality_fraction": locality,
        "locality_sched": {"hits": loc_hits, "total": loc_total},
    }
    print(json.dumps(rec), flush=True)
    return rec


if __name__ == "__main__":
    import sys

    if "--obs-ab" in sys.argv:
        obs_ab_main()
    elif "--batched" in sys.argv:
        batched_main()
    elif "--data-plane" in sys.argv:
        data_plane_main()
    else:
        main()
