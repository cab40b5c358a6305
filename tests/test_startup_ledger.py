"""Set-up accounts for itself (ISSUE 56): the start-up ledger of the
process that holds the chip, the compile listener's split of every first
call into trace / lower / compile-or-cache-load / run, and the driver's and
the controller's events on the same wall clock.  CPU, tiny models: the
numbers here are a CPU's; what is checked is that the parts are all there
and sum to the whole."""

import glob
import math
import sys
import time

import jax
import pytest

import ray_tpu
from ray_tpu._private import compile_cache, startup
from ray_tpu.llm import EngineConfig, LLMEngine
from ray_tpu.models.gpt import GPTConfig, gpt_init

GPT = GPTConfig(vocab_size=64, seq_len=64, d_model=32, n_layers=2, n_heads=2,
                remat=False, fused_loss=False, dtype="float32")
GPT_ENGINE = dict(max_slots=2, num_blocks=24, block_size=4, max_blocks_per_seq=10,
                  prefill_chunk=8)
INIT_PHASES = ("backend_init", "weights", "engine_init", "warmup", "other")


@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    """One in-process replica, built under the profiler: its ledger, and
    the ``startup.*`` spans its phases left on the profiler's clock."""
    from jax.profiler import ProfileData

    from ray_tpu.serve.llm import LLMDeployment

    trace_dir = tmp_path_factory.mktemp("startup-trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        dep = LLMDeployment(model="gpt", model_cfg=GPT,
                            engine_config=EngineConfig(**GPT_ENGINE))
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)[-1]
    spans = {
        e.name: e.duration_ns * 1e-9
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events if e.name.startswith("startup.")
    }
    deadline = time.time() + 30
    while dep.stats()["startup"]["weights_device_done_s"] is None and time.time() < deadline:
        time.sleep(0.01)
    yield dep, spans
    dep._stop.set()
    dep._loop.join(5)


def test_the_replicas_phases_sum_to_its_init(deployment):
    dep, _spans = deployment
    led = dep.stats()["startup"]
    whole = led["t_ready"] - led["t_init_begin"]
    phases = led["phases_s"]
    assert set(INIT_PHASES) <= set(phases)
    assert all(math.isfinite(phases[p]) and phases[p] >= 0.0 for p in INIT_PHASES), phases
    # the phases and the whole are read from two clocks (perf_counter, wall)
    assert abs(sum(phases[p] for p in INIT_PHASES) - whole) < 5e-3
    assert phases["other"] < 0.1 * whole
    # the weights are ready on the device after they were dispatched and
    # (nothing waits for them on the path) no later than the replica is
    assert 0.0 < led["weights_device_done_s"] <= whole + 1.0


@pytest.mark.parametrize("phase", INIT_PHASES[:-1])
def test_a_phase_is_a_span_of_the_same_seconds(deployment, phase):
    dep, spans = deployment
    seconds = dep.stats()["startup"]["phases_s"][phase]
    assert abs(spans["startup." + phase] - seconds) < 0.05 + 0.02 * seconds


def test_a_replica_and_a_plain_process_answer_alike(deployment):
    from ray_tpu.util.device_prof import device_report

    dep, _spans = deployment
    assert dep.device_report()["startup"] == dep.stats()["startup"] == device_report()["startup"]
    assert device_report()["compile_cache"]["programs"] >= 4


def test_the_weights_program_is_counted_with_the_steps(monkeypatch):
    """``ensure_compile_cache()`` comes first in ``__init__``: the table
    has ``make`` (the weights) beside the step programs and the fork."""
    from ray_tpu.serve.llm import LLMDeployment

    monkeypatch.setattr(compile_cache, "_BY_FN", {})
    cfg = GPTConfig(vocab_size=72, seq_len=64, d_model=32, n_layers=1, n_heads=2,
                    remat=False, fused_loss=False, dtype="float32")
    dep = LLMDeployment(model="gpt", model_cfg=cfg, engine_config=EngineConfig(**GPT_ENGINE))
    dep._stop.set()
    dep._loop.join(5)
    by_fn = compile_cache.stats()["by_fn"]
    assert {"make", "_decode_impl", "_prefill_impl", "_prefill_with_slots_impl",
            "_fork_impl"} <= set(by_fn), sorted(by_fn)
    assert len(by_fn) <= compile_cache.BY_FN_ROWS
    # programs first, each group by what it cost
    order = [(r["n"] > 0, r["trace_s"] + r["lower_s"] + r["backend_compile_s"])
             for r in by_fn.values()]
    assert order == sorted(order, reverse=True)


def _gpt_engine():
    return LLMEngine(GPT, gpt_init(jax.random.PRNGKey(0), GPT), EngineConfig(**GPT_ENGINE))


def _brumby_engine():
    from ray_tpu.models.brumby import BrumbyConfig, brumby_init

    cfg = BrumbyConfig(vocab_size=192, seq_len=512, d_model=64, n_layers=2, n_heads=4,
                       n_kv_heads=2, head_dim=16, d_ff=96, dtype="float32",
                       retention_impl="xla")
    return LLMEngine(cfg, brumby_init(jax.random.PRNGKey(0), cfg),
                     EngineConfig(max_slots=4, prefill_chunk=16, prefix_cache=False))


def _falcon_h1_engine():
    from ray_tpu.models.falcon_h1 import FalconH1Config, falcon_h1_init

    cfg = FalconH1Config(vocab_size=192, d_model=64, n_layers=3, n_heads=10, n_kv_heads=2,
                         head_dim=8, d_ff=96, d_ssm=64, ssm_heads=4, d_state=16, n_groups=2,
                         ssm_chunk=4, dtype="float32", attn_impl="xla")
    return LLMEngine(cfg, falcon_h1_init(jax.random.PRNGKey(0), cfg),
                     EngineConfig(max_slots=3, prefill_chunk=8, block_size=4,
                                  max_blocks_per_seq=32, num_blocks=97, prefix_cache=False))


@pytest.mark.parametrize("build,sites", [
    (_gpt_engine, {"prefill", "decode", "prefill_with_slots", "fork"}),  # paged
    (_brumby_engine, {"prefill", "decode"}),                             # state
    (_falcon_h1_engine, {"prefill", "decode"}),                          # hybrid
], ids=["paged", "state", "hybrid"])
def test_a_first_calls_parts_sum_to_the_call(build, sites):
    eng = build()
    eng.warmup()
    rep = eng.device_report()
    assert set(rep["first_call"]) == set(rep["first_call_s"]) >= sites
    for site, parts in rep["first_call"].items():
        assert set(parts) == {"trace_s", "lower_s", "compile_s", "cache_hit", "run_s"}
        seconds = [parts[k] for k in ("trace_s", "lower_s", "compile_s", "run_s")]
        assert abs(sum(seconds) - rep["first_call_s"][site]) < 5e-3, (site, parts)
        # a CPU run keeps no persistent cache: every program was compiled,
        # and each second was counted once (an inner jit's trace is not
        # counted again under the outer's), so something is left to run in
        assert parts["cache_hit"] is False and parts["run_s"] > 0, parts
        # (the fork's jit is the module's: a second engine of a process
        # finds it compiled, and its first call there is all run)
        if site != "fork":
            assert parts["trace_s"] > 0 and parts["compile_s"] > 0, parts
    # a site's later calls record nothing more
    before = {k: dict(v) for k, v in eng.runner.first_call.items()}
    eng.generate([3, 1, 4, 1, 5], ray_tpu.llm.SamplingParams(max_tokens=3))
    assert eng.runner.first_call == before and eng.runner._totals_before is None


def test_the_compile_event_carries_the_split():
    from ray_tpu._private import events

    eng = _gpt_engine()
    eng.warmup()
    mine = [e for e in events.snapshot() if e["type"] == "llm.compile"][-len(eng.runner.first_call):]
    assert {e["fn"] for e in mine} == set(eng.runner.first_call)
    for e in mine:
        assert e["trace_s"] == eng.runner.first_call[e["fn"]]["trace_s"]
        assert e["first_call_s"] == eng.runner.first_call_s[e["fn"]]


def test_a_jitted_function_lands_under_its_name_once(monkeypatch):
    monkeypatch.setattr(compile_cache, "_BY_FN", {})
    compile_cache.ensure_compile_cache()
    compile_cache.ensure_compile_cache()  # one listener still

    def inner(x):
        return x * 2.0 + 1.0

    def _startup_probe(x):
        return jax.jit(inner)(x).sum()

    x = jax.numpy.arange(7.0)  # an eager op compiles a program of its own
    before = compile_cache.stats()
    jax.jit(_startup_probe)(x).block_until_ready()
    after = compile_cache.stats()
    row = after["by_fn"]["_startup_probe"]
    assert row["n"] == 1 and row["trace_s"] > 0 and row["backend_compile_s"] > 0, row
    assert row["lower_s"] > 0
    assert after["programs"] - before["programs"] == 1
    # the inner jit was traced inside the outer: its row has its own
    # seconds, the process's total has them once
    assert after["by_fn"]["inner"]["n"] == 0 and after["by_fn"]["inner"]["trace_s"] > 0
    grew = after["trace_s"] - before["trace_s"]
    assert row["trace_s"] - 1e-6 <= grew + 1e-3 and grew < row["trace_s"] + 1e-3, (grew, row)


def test_both_listeners_count_under_the_lock():
    """``stats()`` reads from another thread than the one that compiles."""
    import threading

    compile_cache.ensure_compile_cache()
    base = compile_cache.stats()
    n, threads = 2000, 4

    def fire():
        for _ in range(n):
            compile_cache._on_event("/jax/compilation_cache/cache_hits")
            compile_cache._on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 1e-3)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(target=fire) for _ in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(60)
            assert not w.is_alive()
    finally:
        sys.setswitchinterval(old)
        with compile_cache._LOCK:
            compile_cache._COUNTS["hits"] -= n * threads
            compile_cache._SECONDS["cache_retrieval_s"] -= 1e-3 * n * threads
    assert abs(compile_cache.stats()["cache_retrieval_s"] - base["cache_retrieval_s"]) < 1e-6
    assert compile_cache.stats()["hits"] == base["hits"]


# ---------------------------------------------------------------- processes


@pytest.fixture
def cluster():
    ray_tpu.init(num_cpus=2)
    try:
        yield
    finally:
        ray_tpu.shutdown()


def test_a_worker_that_never_computes_stamps_its_start_and_imports_no_jax(cluster):
    @ray_tpu.remote
    def probe():
        import sys

        from ray_tpu._private import startup

        return "jax" in sys.modules, startup.report()

    t0 = time.time()
    has_jax, led = ray_tpu.get(probe.remote(), timeout=60)
    assert has_jax is False
    # forked from the template at the request, or exec'd fresh since init
    assert t0 - 60 < led["t_process_start"] <= time.time()
    assert led["t_init_begin"] is None and led["phases_s"] == {}


def test_a_train_worker_reports_its_boot_and_the_driver_its_spawn(cluster, tmp_path):
    from ray_tpu import train
    from ray_tpu._private import events
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    def loop(config):
        from ray_tpu._private import startup

        train.report({"startup": startup.report(), "t_loop": time.time()})

    t0 = time.time()
    result = JaxTrainer(
        loop, scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="startup", storage_path=str(tmp_path)),
    ).fit()
    led = result.metrics["startup"]
    assert t0 <= led["t_process_start"] <= led["t_init_begin"] <= result.metrics["t_loop"]
    boot = led["phases_s"]["worker_boot"]
    assert boot == led["t_init_begin"] - led["t_process_start"] and 0 <= boot < 60
    assert led["t_ready"] is None and "other" not in led["phases_s"]
    ev = [e for e in events.snapshot() if e["type"] == "train.worker_start"][-1]
    assert ev["loop_entered_at"] == led["t_init_begin"]
    assert 0 < ev["spawn_s"] < time.time() - t0


def test_the_controller_says_when_the_replica_was_ready_and_when_it_noticed(cluster):
    from ray_tpu import serve
    from ray_tpu._private import events

    @serve.deployment
    class Slow:
        def __init__(self):
            time.sleep(0.3)

        def __call__(self, x):
            return x + 1

    t0 = time.time()
    try:
        handle = serve.run(Slow.bind(), name="slow")
        assert handle.remote(1).result() == 2
        evs = events.collect_cluster_events(timeout=10.0)
    finally:
        serve.shutdown()
    init = [e for e in evs if e.get("type") == "serve.replica_initialized"][-1]
    assert t0 + 0.3 <= init["ready_at"] <= init["ts"]
    assert abs(init["detect_lag_s"] - (init["ts"] - init["ready_at"])) < 0.01
    assert init["detect_lag_s"] <= init["init_s"]
    run = [e for e in events.snapshot() if e["type"] == "serve.run"][-1]
    parts = [run[k] for k in ("controller_s", "deploy_s", "proxy_s", "wait_ready_s")]
    assert all(p >= 0 for p in parts) and run["wait_ready_s"] >= 0.25
    assert sum(parts) <= time.time() - t0
