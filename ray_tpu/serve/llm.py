"""serve.llm: stream-first LLM serving on top of ``ray_tpu.llm``.

``LLMDeployment`` runs one ``LLMEngine`` inside each replica: a daemon
thread turns the engine crank while replica request threads submit and
stream.  ``__call__`` is a GENERATOR, so the serve stack's existing
streaming-generator machinery does the rest — callers use

    handle = serve.run(build_llm_app(model="gptj", model_cfg=cfg))
    for tok in handle.options(stream=True).remote([1, 2, 3],
                                                  max_tokens=32):
        ...

and tokens cross the cluster as they are sampled (TTFT ≈ one prefill +
one decode step, not the whole completion).  ``generate`` is the
blocking whole-completion method for non-streaming callers (a generator
return can't pickle through ``handle_request``).

Autoscaling: the replica exports the engine's queue depth and KV-cache
utilization — through ``util.metrics`` gauges (``llm_*`` series) and
through ``autoscaling_metrics()``, which the serve controller's scaling
decision CONSUMES (``_private/controller.desired_replicas``: queued
requests count as load; a KV-saturated replica adds upscale pressure).
Since a continuous-batching replica absorbs many concurrent requests per
slot set, ongoing-request counts alone under-report saturation; queue
depth (> 0 means the engine is admission-bound) and KV utilization
(≈ 1.0 means preemption-bound) are the honest signals.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from ray_tpu._private import startup as _startup
from ray_tpu._private import stream_sink

# a replica's worker first meets jax HERE, unpickling its actor's class:
# the start-up ledger's ``import`` is this module's own imports, timed
_t_import = time.perf_counter()
from ray_tpu._private.compile_cache import ensure_compile_cache  # noqa: E402
from ray_tpu.llm.engine import EngineConfig, LLMEngine, stream_stats  # noqa: E402
from ray_tpu.llm.scheduler import FINISH_CANCELLED, SamplingParams  # noqa: E402

_startup.imported(time.perf_counter() - _t_import)


def _seeded_params(init, cfg, seed: int, tp: int):
    """Seeded random weights made by ONE jitted program, in the model's
    compute dtype, straight into the placement the runner uses.  The fp32
    masters ``init`` describes never materialize (GPT-J-6B's would be
    24 GB on a 16 GB chip): XLA fuses each leaf's generation with its
    cast, and under ``tp > 1`` the tp placement as ``out_shardings``
    makes every device generate only its own shard."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(cfg.dtype)

    def make():
        params = init(jax.random.PRNGKey(seed), cfg)
        return jax.tree_util.tree_map(lambda x: x.astype(dt), params)

    shardings = None
    if tp > 1:
        from ray_tpu.llm.multichip import param_shardings

        shardings = param_shardings(jax.eval_shape(make), tp)
    return jax.jit(make, out_shardings=shardings)()


#: served families: name -> (module, configuration class, initializer, what
#: stands in for a missing ``model_cfg``: the class itself, or an instance)
_FAMILIES = {
    "gptj": ("ray_tpu.models.gptj", "GPTJConfig", "gptj_init", "GPTJ_6B"),
    "gpt": ("ray_tpu.models.gpt", "GPTConfig", "gpt_init", "GPTConfig"),
    "brumby": ("ray_tpu.models.brumby", "BrumbyConfig", "brumby_init", "BrumbyConfig"),
    "phi4flash": ("ray_tpu.models.phi4flash", "Phi4FlashConfig", "phi4flash_init",
                  "Phi4FlashConfig"),
    "kimi_k2": ("ray_tpu.models.kimi_k2", "KimiK2Config", "kimi_k2_init", "KimiK2Config"),
    "falcon_h1": ("ray_tpu.models.falcon_h1", "FalconH1Config", "falcon_h1_init",
                  "FalconH1Config"),
    "granite_h": ("ray_tpu.models.granite_h", "GraniteHConfig", "granite_h_init",
                  "GraniteHConfig"),
    "lfm2_moe": ("ray_tpu.models.lfm2", "Lfm2MoeConfig", "lfm2_moe_init", "Lfm2MoeConfig"),
    "nemotron_h": ("ray_tpu.models.nemotron_h", "NemotronHConfig", "nemotron_h_init",
                   "NemotronHConfig"),
    "afmoe": ("ray_tpu.models.afmoe", "AfmoeConfig", "afmoe_init", "AfmoeConfig"),
}


def _build_model(model: str, model_cfg, params, seed: int, tp: int = 1):
    """Materialize (cfg, params) inside the replica — shipping a seed
    instead of a parameter pytree keeps deployment specs small and lets
    each replica initialize straight onto its own device(s)."""
    import importlib

    if model not in _FAMILIES:
        raise ValueError(
            f"unknown model family {model!r}; expected one of "
            + ", ".join(repr(name) for name in _FAMILIES)
        )
    module, cfg_name, init_name, default = _FAMILIES[model]
    mod = importlib.import_module(module)
    cfg_cls, default = getattr(mod, cfg_name), getattr(mod, default)
    cfg = model_cfg or (default() if isinstance(default, type) else default)
    if not isinstance(cfg, cfg_cls):
        raise TypeError(f"model_cfg must be a {cfg_name}, got {type(cfg).__name__}")
    if params is None:
        params = _seeded_params(getattr(mod, init_name), cfg, seed, tp)
    return cfg, params


class LLMDeployment:
    """The replica callable. Decorate/bind via ``build_llm_app`` (or apply
    ``serve.deployment`` yourself for custom replica options)."""

    def __init__(
        self,
        model: str = "gptj",
        model_cfg=None,
        params: Optional[dict] = None,
        engine_config: Optional[EngineConfig] = None,
        seed: int = 0,
        warmup: bool = True,
        stream_timeout_s: float = 300.0,
        draft_model_cfg=None,
        draft_params: Optional[dict] = None,
    ):
        # set-up accounts for itself (``_private.startup``): each phase
        # below is a ``startup.<phase>`` span and a line of the ledger that
        # ``device_report()`` and ``stats()`` carry
        _startup.init_begin()
        with _startup.phase("backend_init"):
            import jax

            # the first touch of the backend, which used to hide inside
            # the weights' first jit; the compile listener starts BEFORE
            # that program compiles, so it is counted (and cached) too
            jax.devices()
            ensure_compile_cache()
        tp = engine_config.tp if engine_config is not None else 1
        with _startup.phase("weights"):  # host time to dispatch, no wait
            cfg, params = _build_model(model, model_cfg, params, seed, tp)
            # speculative decoding with the small-model drafter
            # (engine_config.spec_drafter == "model"): the draft model's
            # config + params pass straight through to the engine; the
            # default n-gram drafter needs neither
            if draft_model_cfg is not None and draft_params is None:
                _, draft_params = _build_model(
                    model, draft_model_cfg, None, seed
                )
        # one leaf stands for all (a seeded tree is ONE program's results):
        # the waiter thread must not keep alive the kernels a runner re-packs
        _startup.stamp_when_ready(jax.tree_util.tree_leaves(params)[-1:])
        #: max wait for the next streamed token — must cover the ADMISSION
        #: wait of a request queued behind a saturated engine, not just
        #: inter-token gaps (the engine's own 60s default is too tight for
        #: a deployment whose whole point is absorbing a deep queue)
        self._stream_timeout_s = stream_timeout_s
        with _startup.phase("engine_init"):
            # the engine TAKES the tree (``[tree].pop``: no reference stays
            # in this frame), so that kernels its runner keeps in another
            # form are on the device once when the pool is made, not twice
            handed = [params]
            del params
            self._engine = LLMEngine(
                cfg, handed.pop, engine_config,
                draft_model_cfg=draft_model_cfg, draft_params=draft_params,
            )
            # per-engine watchdog (llm.watchdog): stall detection, wedge-proof
            # deadline/cancel reaping, KV-pool leak audit — a serving replica
            # always runs one
            self._engine.start_watchdog()
        if warmup:
            # compile the prefill/decode/verify/sampling jits NOW, inside
            # replica creation, so serve.run's readiness gate covers
            # compile time and the first real request streams at
            # steady-state latency (covers BOTH decode paths of a
            # speculating engine — see LLMEngine.warmup)
            with _startup.phase("warmup"):
                self._engine.warmup()
        self._stop = threading.Event()
        self._loop = threading.Thread(
            target=self._engine.run_loop, args=(self._stop,),
            name="llm-engine-loop", daemon=True,
        )
        self._loop.start()
        _startup.ready()

    # -- request path ------------------------------------------------------

    def __call__(
        self,
        prompt: list,
        max_tokens: int = 16,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        stop_token_ids: tuple = (),
        seed: int = 0,
        deadline_s: Optional[float] = None,
        resume_tokens: tuple = (),
    ):
        """Streaming generation: yields token ids as the engine samples
        them. Call with ``handle.options(stream=True)``; the generator
        shape is what routes this through ``handle_request_streaming``.

        ``prompt`` may also be a dict — ``{"prompt": [...], "max_tokens":
        32, "temperature": 0.8, ...}`` — so HTTP callers (whose JSON body
        arrives as the single positional payload) can set sampling knobs
        and a ``deadline_s``; dict keys override the keyword defaults.

        ``resume_tokens`` is the mid-stream failover journal (the handle
        layer injects it via the deployment's ``stream_resume_arg``
        contract): tokens a dead replica already delivered. Generation
        continues AFTER them, token-identically (``LLMEngine.submit``).
        A dict payload's own ``resume_tokens`` (a client-side resume)
        concatenates with the handle-injected journal.
        """
        if isinstance(prompt, dict):
            body = dict(prompt)
            try:
                prompt = body.pop("prompt")
            except KeyError:
                raise ValueError("dict payload requires a 'prompt' key") from None
            max_tokens = body.pop("max_tokens", max_tokens)
            temperature = body.pop("temperature", temperature)
            top_k = body.pop("top_k", top_k)
            top_p = body.pop("top_p", top_p)
            stop_token_ids = body.pop("stop_token_ids", stop_token_ids)
            seed = body.pop("seed", seed)
            deadline_s = body.pop("deadline_s", deadline_s)
            # client-resumed prefix first, then the failover journal
            resume_tokens = tuple(body.pop("resume_tokens", ())) + tuple(
                resume_tokens
            )
            if body:
                raise ValueError(f"unknown payload keys: {sorted(body)}")
        params = SamplingParams(
            max_tokens=max_tokens,
            temperature=temperature,
            top_k=top_k,
            top_p=top_p,
            stop_token_ids=tuple(stop_token_ids),
            seed=seed,
        )
        # a streaming task's body takes over its stream's sink: the engine
        # then hands every row's token of a step to ONE sender and this
        # thread wakes once, at the request's end (None under a plain call
        # such as ``generate``: the tokens are yielded one by one as ever)
        sink = stream_sink.adopt()
        req = self._engine.submit(
            [int(t) for t in prompt], params, deadline_s,
            resume_tokens=tuple(int(t) for t in resume_tokens),
            sink=sink,
        )
        if sink is not None:
            # a consumer that walks away ends the wait below at once
            sink.on_cancel(lambda: req.stream.put(("done", FINISH_CANCELLED)))
        # with an explicit deadline the engine itself ends the stream at
        # the deadline; the get-timeout only needs to outlast it
        timeout = (
            deadline_s + 5.0 if deadline_s is not None else self._stream_timeout_s
        )
        try:
            yield from self._engine.stream_tokens(req, timeout=timeout)
        finally:
            # consumer walked away (stream closed/replica thread unwinding):
            # stop generating for nobody
            if not req.finished:
                self._engine.cancel(req.id)

    def generate(self, prompt: list, **kwargs) -> list:
        """Blocking whole-completion variant for non-streaming callers."""
        return list(self.__call__(prompt, **kwargs))

    # -- control plane -----------------------------------------------------

    def update_weights(self, update, version=None, timeout: float = 120.0) -> int:
        """Versioned weight hot-swap — the SAME push path raw actor
        engines use (``rlhf.sync.apply_weight_update`` →
        ``LLMEngine.update_weights``): accepts a published
        ``rlhf.sync.WeightUpdate`` manifest (chunked object-plane refs)
        or a raw params pytree + ``version``, and applies it between
        engine steps WITHOUT draining in-flight streams. An RLHF learner
        can therefore push to serve-hosted inference replicas and
        dedicated rollout actors with one code path:

            handle.update_weights.remote(weight_update).result()

        Routes like any other handle call (one replica per call); push
        once per replica — or use ``num_replicas=1`` engines for rollout
        duty — when every replica must advance."""
        from ray_tpu.rlhf.sync import WeightUpdate, apply_weight_update

        if not isinstance(update, WeightUpdate):
            # version=None lets LLMEngine.update_weights bump UNDER its
            # lock — computing current+1 here would race a concurrent
            # push into two different param sets sharing one version
            update = (update, version)
        return apply_weight_update(self._engine, update, timeout=timeout)

    def weights_version(self) -> int:
        return self._engine.weights_version

    def autoscaling_metrics(self) -> dict:
        """Saturation signals for replica autoscaling: ``queue_depth``
        (admission-bound) and ``kv_utilization`` (memory-bound) on top of
        the running count the controller already polls.
        ``prefix_hit_rate`` rides along informationally — the
        cross-request prefix cache (``llm.prefix_cache``) is per-replica,
        so routing that keeps a tenant's traffic on one replica (session
        affinity, a ROADMAP item) shows up directly as a higher hit rate
        here.  Note ``kv_utilization`` counts only blocks live requests
        hold: cache-only residents are evictable on demand and never
        create upscale pressure.  Under tensor parallelism (``tp > 1``)
        it stays POOL-WIDE, not per-shard: block ids are global across
        the mesh (llm.multichip), so the pool-wide fraction IS each
        device's fraction and the honest saturation signal."""
        s = self._engine.stats()
        m = {
            "queue_depth": s["queue_depth"],
            "kv_utilization": s["kv_utilization"],
            "running": s["running"],
            "waiting": s["waiting"],
        }
        if "prefix_cache" in s:
            m["prefix_hit_rate"] = s["prefix_cache"]["hit_rate"]
        return m

    def stats(self) -> dict:
        """The engine's counters, under ``"stream"`` the replica
        process's station histograms of the streaming path (read after the
        engine answered, without its lock: OBSERVABILITY.md, "The streamed
        token's stations"), and under ``"startup"`` the process's start-up
        ledger (OBSERVABILITY.md, "Start-up ledger")."""
        s = self._engine.stats()
        s["stream"] = stream_stats()
        s["startup"] = _startup.report()
        return s

    def audit(self) -> dict:
        """The KV-pool ledger and prefix-tree audits the watchdog runs
        every tick (``KVBlockPool.audit`` / ``PrefixCache.audit``), on
        demand."""
        cache = self._engine.prefix_cache
        return {
            "pool": self._engine.pool.audit(),
            "prefix_cache": cache.audit() if cache is not None else None,
        }

    def device_report(self) -> dict:
        """What this replica's engine runs on and what its compiled steps
        contain (``LLMEngine.device_report``)."""
        return self._engine.device_report()

    def start_trace(self, log_dir: str) -> str:
        """Start a ``jax.profiler`` trace of THIS replica into ``log_dir``
        (only the process that holds the chip can trace it).  Device ops
        and the engine's ``llm.*`` annotations (OBSERVABILITY.md, "Engine
        step timeline") land in one ``.xplane.pb`` on one clock.  The
        Python tracer stays off: it slows the host loop, which is what an
        idle share of the device measures."""
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        return log_dir

    def stop_trace(self) -> None:
        """Stop the trace and write it out.  This takes seconds of the
        replica's time (4-65 s after a 3 s slice of GPT-J decode on a v5e,
        PERF.md): read window counters BEFORE calling it."""
        import jax

        jax.profiler.stop_trace()

    def check_health(self) -> None:
        if not self._loop.is_alive():
            raise RuntimeError("LLM engine loop thread died")

    def __del__(self):
        try:
            self._stop.set()
        except Exception:  # raylint: disable=RL007
            pass  # interpreter teardown: the daemon thread dies with us


def build_llm_app(
    model: str = "gptj",
    model_cfg=None,
    engine_config: Optional[EngineConfig] = None,
    seed: int = 0,
    num_replicas: int = 1,
    max_ongoing_requests: int = 16,
    autoscaling_config=None,
    name: str = "LLMDeployment",
    warmup: bool = True,
    tp: Optional[int] = None,
):
    """Bind an ``LLMDeployment`` application (deploy with ``serve.run``).

    ``tp`` — tensor parallelism per replica (``llm.multichip``): a
    convenience overlay on ``engine_config.tp`` so app builders can
    shard replicas over the tp mesh without constructing an
    ``EngineConfig``.  Each replica builds its own mesh over the first
    ``tp`` visible devices.  ``autoscaling_metrics`` keeps reporting the
    POOL-WIDE ``kv_utilization`` — the block ledger is host-global under
    tp (every device holds the same blocks' local heads), so a per-shard
    number would just repeat it ``tp`` times and a partial one would
    under-report saturation to the controller.

    ``max_ongoing_requests`` should comfortably exceed the engine's
    ``max_slots`` — the whole point of continuous batching is holding
    more concurrent streams than decode slots and letting the engine's
    queue absorb the difference (queue depth then drives autoscaling).

    ``warmup=True`` (default) compiles inside replica ``__init__`` so the
    readiness gate covers jit time and first requests stream at
    steady-state latency. ``warmup=False`` trades that for FAST replica
    (re)join: a replacement replica becomes routable in seconds and pays
    compile inside its first request — the right trade when replicas
    churn (chaos, spot preemption) and a mid-stream failover must find a
    routable successor before the router's pick deadline, not after a
    full warmup.
    """
    from ray_tpu.serve.api import deployment

    if tp is not None:
        import dataclasses

        engine_config = dataclasses.replace(
            engine_config or EngineConfig(), tp=tp
        )
    dep = deployment(
        LLMDeployment,
        name=name,
        num_replicas=num_replicas,
        max_ongoing_requests=max_ongoing_requests,
        autoscaling_config=autoscaling_config,
        # mid-stream failover contract: a stream whose replica dies is
        # re-submitted with resume_tokens=<delivered tokens> and resumes
        # token-identically; deadline_s is re-submitted MINUS the time
        # already spent, so failovers never extend a client's declared
        # wait budget (RESILIENCE.md)
        stream_resume_arg="resume_tokens",
        stream_deadline_arg="deadline_s",
    )
    return dep.bind(
        model=model, model_cfg=model_cfg, engine_config=engine_config,
        seed=seed, warmup=warmup,
    )
