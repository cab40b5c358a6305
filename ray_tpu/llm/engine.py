"""LLMEngine: the continuous-batching step loop.

One engine owns one model (GPT or GPT-J params), one paged KV pool, and
one scheduler.  A model without keys and values (``cache_kind == "state"``:
``models.brumby``) gets ``cache.StatePool`` and ``state_runner`` behind the
same calls: a sequence owns one fixed-size recurrent state, so admission is
a free slot, nothing grows or is preempted, and the prefix cache,
speculation and ``tp > 1`` are refused.  A model with BOTH
(``cache_kind == "hybrid"``: ``models.phi4flash``, ``models.falcon_h1``,
``models.granite_h``) gets
``cache.HybridPool`` and ``state_runner.HybridModelRunner``: a slot of state
and growing blocks of K/V (as many layers of them as the family's
``kv_layout()`` says: one shared layer, or every layer's) behind one ledger,
admitted when both are free, preempted for blocks, refused the same three.  A family whose body keeps blocks
ALONE, of whatever it says a token leaves behind (``cache_kind == "paged"``:
``models.kimi_k2``, one array of latent rows), gets the same runner over a
``cache.KVBlockPool`` of that layout: its blocks are shared, forked and
evicted as GPT-J's, so the prefix cache runs, and only speculation and
``tp > 1`` are refused.  A family whose layers are of TWO kinds, full and
window attention (``cache_kind == "windowed"``: ``models.afmoe``), gets that
runner over a ``cache.LayerTypedPool``: every block of a sequence in the full
layers, in the window layers only the blocks a later query still sees (slid
before each prefill chunk and each decode); the same three are refused.
``step()`` is the whole design:

1. reap cancellations and blown deadlines;
2. admit waiting requests into free decode slots (FIFO, memory-gated,
   and — with the prefix cache on — CACHE-AWARE: the longest cached
   prefix is shared into the new block table, LRU cache eviction runs
   before anyone is preempted, and queued copy-on-write forks are
   applied as one batched device copy);
3. run ONE chunked-prefill piece for the oldest still-prefilling
   admission — interleaved with, never instead of, decode; completed
   prompt blocks are inserted into the prefix tree as they fill.  Where
   the runner offers it (``PagedModelRunner.prefill_with_slots``: the
   one-chip string path) and rows decode, the chunk goes out IN the
   decode's launch below, one program and one pass over the weights
   (``_chunk_rides``); into an empty batch, under a drafter and on the
   other runners it is a launch of its own, in front of the decode's;
4. run ONE batched decode step across every running slot (single jitted
   call, static slot count), sample per-slot tokens (per-request
   temperature/top-k/top-p/seed), stream them out, finish requests that
   hit ``max_tokens``/stop tokens, preempting the youngest when the
   block pool runs dry.  The decode is ONE STEP AHEAD of the host: what a
   slot feeds lives on the device (``model_runner`` "Slot state"), so step
   N+1 is launched before step N's tokens are read, and the device runs it
   under the emit, the gauges, the lock hand-over and the next admit
   ("The decode in flight" below).  With ``spec_k > 0`` the decode step is
   SPECULATIVE: a drafter (``llm.drafter``) proposes ``k`` tokens per
   slot, the target model verifies all ``k+1`` positions in one jitted
   call (``model_runner.verify_step``), and each slot emits its accepted
   prefix plus a correction/bonus token — up to ``k+1`` tokens per step
   at one target-model invocation, token-identical under greedy and
   distribution-exact under sampling (``models.sampling``).

Observability: every step is a ``util.tracing`` span; tokens/s, TTFT,
inter-token latency, running/waiting counts, KV-block utilization and
preemptions publish through ``util.metrics`` (the same surface the serve
autoscaler and Grafana boards read).  The step also accounts for ITSELF:
each host phase (``STEP_PHASES``) is timed once with ``perf_counter`` and
that one timing feeds a ``jax.profiler.TraceAnnotation`` named
``llm.step.<phase>`` (the device trace's clock: an idle gap of the chip
gets the name of what the host was doing) and a cumulative counter in
``stats()`` (``step_phase_s``, ``loop``, ``submit``, ``queue``: the whole
window, not a traced slice).  OBSERVABILITY.md, "Engine step timeline".

The decode in flight: ``_flight`` is the launched step whose tokens are
still on the device.  A step launches the next decode first and reads
``_flight`` after, and after that a final prefill chunk's first token,
sampled inside the prefill program (``_first``).  A final chunk that went
out alone lets its row decode in the SAME step (``PATCH_JOIN``: the token
never visits the host); one that rode the decode's launch leaves its token
beside that launch's, so the row joins the NEXT launch the same way and
the token is read a step on, right after the flight it rode.  A row is
left out of a launch once its LAST token is sampled (``max_tokens`` / the model length,
counted with the tokens in flight); a stop token shows one step late, so
that row's extra token is dropped at the read (``discarded_tokens``; its
blocks were freed when it finished, and the device runs its programs in
launch order, so whoever holds them next writes after the stray write).
What cannot run ahead reads the device dry first (``_drain``): a cancel or
a blown deadline of a row in flight, a growth that has to preempt, a weight
swap, a batch gone empty, the loop's stop.  A drafter needs every token on
the host, so a speculating engine reads each decode at once (depth 0).
``stats()["pipeline"]`` counts all of it.

Threading: ``step()`` serializes on an internal lock — any number of
submitter threads (serve replica handlers) can feed the engine while one
driver thread (or several, harmlessly) turns the crank.
"""

from __future__ import annotations

import bisect
import dataclasses
import threading
import time
from typing import Iterator, Optional

import numpy as np

from ray_tpu._private import events as _events
from ray_tpu._private import stream_stats as _stream_stats
from ray_tpu.llm.cache import (
    CacheConfig,
    HybridConfig,
    HybridPool,
    LayerTypedConfig,
    LayerTypedPool,
    KVBlockPool,
    StateConfig,
    StatePool,
)
from ray_tpu.llm.model_runner import (
    PATCH_JOIN,
    PATCH_SET,
    PagedModelRunner,
    pack_knobs,
)
from ray_tpu.models.sampling import sort_ladder, sort_rung
from ray_tpu.util import phases as _phases
from ray_tpu.util import tracing as _tracing
from ray_tpu.llm.scheduler import (
    FINISH_CANCELLED,
    FINISH_DEADLINE,
    FINISH_LENGTH,
    FINISH_STOP,
    FINISHED,
    PREFILL,
    RUNNING,
    Request,
    SamplingParams,
    Scheduler,
)

#: every metric the engine exports — the RL012 drift gate cross-checks
#: this registry against the constructors in ``_metrics()`` (both
#: directions), so a renamed metric cannot silently orphan its dashboard
#: panel or doc row
METRIC_NAMES = (
    "llm_generated_tokens",
    "llm_prefill_tokens",
    "llm_engine_steps",
    "llm_finished_requests",
    "llm_preemptions",
    "llm_running_requests",
    "llm_waiting_requests",
    "llm_kv_block_utilization",
    "llm_time_to_first_token_s",
    "llm_inter_token_latency_s",
    "llm_spec_draft_tokens",
    "llm_spec_accepted_tokens",
    "llm_spec_acceptance_rate",
    "llm_spec_draft_seconds",
    "llm_tokens_per_step",
    "llm_shed_requests",
    # HBM ledger: who holds device memory (the tiered-KV spill decision's
    # signal) — params, pool blocks split seq-owned vs cache-only
    # resident vs free, drafter state; conservation against
    # KVBlockPool.audit() is pinned by tests/test_profiling_plane.py
    "llm_hbm_params_bytes",
    "llm_hbm_kv_pool_bytes",
    "llm_hbm_kv_seq_bytes",
    "llm_hbm_kv_cache_bytes",
    "llm_hbm_kv_free_bytes",
    "llm_hbm_drafter_bytes",
)

#: the host phases of one step, in the order a step runs them: the keys
#: of ``stats()["step_phase_s"]``, each the name of its span on the
#: profiler's clock behind ``llm.step.`` (a speculative step's
#: ``verify_launch`` / ``verify_fetch`` spans add into the decode keys:
#: the verify call IS that step's decode).  ``decode_fetch`` reads the
#: decode launched a step EARLIER; ``prefill_sample`` is the wait for a
#: final chunk's first token, after that decode's tokens are out;
#: ``drain`` reads and emits what is in flight out of turn (``_drain``)
STEP_PHASES = (
    "admit", "prefill_build", "prefill_launch", "prefill_sample", "draft",
    "decode_build", "decode_launch", "decode_fetch", "emit", "publish", "drain",
)
#: upper bounds (seconds) of ``stats()["loop"]["step_wall_hist"]``: a factor
#: of √2 apart from 1 ms to 65.5 s, then one overflow bucket — a window's
#: delta of the histogram says whether ANY step took seconds
STEP_WALL_BOUNDS_S = tuple(0.001 * 2.0 ** (i / 2) for i in range(33))

class _Flight:
    """A launched decode whose tokens are still on the device: the
    ``(slot, request)`` rows it sampled for and the two arrays to read.
    ``first``: the ``(request, token, logprob)`` of a FINAL chunk that rode
    this launch (``_first`` once the step's reads are done), else None."""

    __slots__ = ("rows", "ids", "nxt", "logp", "step", "first")

    def __init__(self, rows, nxt, logp, step):
        self.rows = rows
        self.ids = {r.id for _, r in rows}
        self.nxt, self.logp, self.step = nxt, logp, step
        self.first = None


class _Phase:
    """One host phase of a step: entered, it opens the phase's span on the
    profiler's clock; left, it closes the span and adds the seconds to the
    phase's counter.  One ``perf_counter`` pair, two readers."""

    __slots__ = ("acc", "key", "ann", "t0")

    def __init__(self, acc: dict, key: str, span: str):
        self.acc, self.key = acc, key
        self.ann = _tracing.annotate(span)

    def __enter__(self):
        self.ann.__enter__()
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        self.acc[self.key] += time.perf_counter() - self.t0
        return self.ann.__exit__(*exc)


_METRICS = None
_METRICS_LOCK = threading.Lock()


def stream_stats() -> dict:
    """``LLMDeployment.stats()["stream"]``: this process's bucket vectors
    of the streamed token's stations (``_private.stream_stats``), ``emit``
    being ``llm_inter_token_latency_s``.  They are the metric registry's,
    not the engine's: read WITHOUT ``LLMEngine._lock``."""
    return _stream_stats.snapshot(emit=_metrics()["itl"].buckets())


def _metrics() -> dict:
    """Engine metric set, created once per process (util.metrics registers
    globally; duplicates would fight in collect())."""
    global _METRICS
    if _METRICS is not None:
        return _METRICS  # lock-free fast path: called per token in _emit
    with _METRICS_LOCK:
        if _METRICS is not None:
            return _METRICS
        from ray_tpu.util.metrics import (
            FINE_LATENCY_BOUNDS_S,
            Counter,
            Gauge,
            Histogram,
        )

        _METRICS = {
            "tokens": Counter("llm_generated_tokens", "tokens sampled by the engine"),
            "prefill_tokens": Counter(
                "llm_prefill_tokens",
                "prompt tokens actually computed by prefill (a prefix-cache "
                "hit skips the matched head, so this is the MISS work)",
            ),
            "steps": Counter("llm_engine_steps", "engine step-loop iterations"),
            "finished": Counter(
                "llm_finished_requests", "requests finished for any reason"
            ),
            "preempt": Counter("llm_preemptions", "requests evicted under KV pressure"),
            "running": Gauge("llm_running_requests", "requests holding decode slots"),
            "waiting": Gauge("llm_waiting_requests", "requests queued for admission"),
            "kv_util": Gauge("llm_kv_block_utilization", "fraction of KV blocks in use"),
            "ttft": Histogram("llm_time_to_first_token_s", "submit → first token"),
            # the `emit` station of the streaming path (_private.
            # stream_stats): a stream's token gap where the loop emits it,
            # on the boundaries every later station shares
            "itl": Histogram(
                "llm_inter_token_latency_s",
                "gap between consecutive streamed tokens",
                boundaries=FINE_LATENCY_BOUNDS_S,
            ),
            # speculative decode: drafted vs accepted counters give the
            # lifetime acceptance rate; the gauges give the latest step's
            "spec_proposed": Counter(
                "llm_spec_draft_tokens", "draft tokens proposed for verification"
            ),
            "spec_accepted": Counter(
                "llm_spec_accepted_tokens", "draft tokens accepted by verification"
            ),
            "spec_accept_rate": Gauge(
                "llm_spec_acceptance_rate", "accepted/proposed of the last step"
            ),
            "spec_draft_s": Counter(
                "llm_spec_draft_seconds", "cumulative wall time inside the drafter"
            ),
            "tokens_per_step": Gauge(
                "llm_tokens_per_step", "tokens emitted by the last decode step"
            ),
            "shed": Counter(
                "llm_shed_requests",
                "requests rejected by deadline-aware admission (429 upstream)",
            ),
            # HBM ledger gauges: live byte accounting of device memory.
            # params + pool + drafter is (approximately) the resident
            # footprint; the three kv_* gauges PARTITION the pool's
            # usable blocks (seq-owned + cache-only + free), so the
            # tiered-KV spill decision can read exactly how much HBM a
            # host-RAM tier would reclaim (cache-only bytes).
            # tag_keys: under tp>1 (llm.multichip) every family is
            # ADDITIONALLY published per mesh device as {device=<id>};
            # the untagged series stays the pool-wide truth either way
            "hbm_params": Gauge(
                "llm_hbm_params_bytes", "device bytes held by model params",
                tag_keys=("device",),
            ),
            "hbm_pool": Gauge(
                "llm_hbm_kv_pool_bytes",
                "total device bytes of the KV pool arrays (fixed at start)",
                tag_keys=("device",),
            ),
            "hbm_seq": Gauge(
                "llm_hbm_kv_seq_bytes",
                "bytes of KV blocks owned by at least one live sequence",
                tag_keys=("device",),
            ),
            "hbm_cache": Gauge(
                "llm_hbm_kv_cache_bytes",
                "bytes of KV blocks resident ONLY in the prefix cache "
                "(reclaimable without preempting anyone)",
                tag_keys=("device",),
            ),
            "hbm_free": Gauge(
                "llm_hbm_kv_free_bytes", "bytes of free-list KV blocks",
                tag_keys=("device",),
            ),
            "hbm_drafter": Gauge(
                "llm_hbm_drafter_bytes",
                "device bytes held by the speculative drafter's params",
                tag_keys=("device",),
            ),
        }
    return _METRICS


def _tree_device_bytes(params) -> int:
    """Total ``nbytes`` across a param pytree (0 for None — the n-gram
    drafter holds no device state)."""
    if params is None:
        return 0
    import jax

    return sum(
        int(getattr(leaf, "nbytes", 0))
        for leaf in jax.tree_util.tree_leaves(params)
    )


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine geometry. ``num_blocks`` includes the reserved trash block;
    ``max_blocks_per_seq * block_size`` caps a sequence (prompt + output),
    additionally clamped by the model's positional table for GPT.

    Speculative decoding: ``spec_k > 0`` turns it on — each step a drafter
    proposes ``spec_k`` tokens per running slot and the target model
    verifies all of them plus a bonus position in ONE jitted call
    (``llm.drafter`` module doc).  ``spec_drafter`` is ``"ngram"``
    (model-free prompt lookup; ``spec_ngram_max`` caps the matched n-gram)
    or ``"model"`` (a small draft model passed to ``LLMEngine`` as
    ``draft_model_cfg``/``draft_params``; ``spec_draft_ctx`` fixes its
    context window).  Greedy output is token-identical either way; ``k``
    trades verification width against acceptance — 2-4 fits most
    workloads, higher only pays when acceptance stays near 1.

    Adversarial (low-acceptance) workloads are bounded by backoff: when a
    verify step accepts less than ``spec_min_accept`` of its drafts, the
    engine falls back to plain decode for exponentially more steps
    (doubling up to ``spec_backoff_max``) before probing speculation
    again — a regime change (output entering a repetitive stretch) is
    picked back up at the next probe, while steady low acceptance decays
    to plain-decode cost plus one probe in ``spec_backoff_max``.  Both
    step shapes are jitted once; toggling never retraces.

    Prefix cache: ``prefix_cache`` (default ON) shares KV blocks across
    requests through a radix tree over block contents
    (``llm.prefix_cache``): admission matches the longest cached prefix
    and prefills only the uncached suffix, with copy-on-write forks on
    intra-block divergence (``prefix_cow_min_tokens`` sets the minimum
    intra-block match worth a device block copy).  Outputs are
    token-identical with the cache on or off — prefix reuse is exact,
    never approximate — and cached blocks are evicted LRU-first under
    pool pressure before any live request is preempted."""

    max_slots: int = 4
    num_blocks: int = 128
    block_size: int = 16
    max_blocks_per_seq: int = 32
    prefill_chunk: int = 32
    attn_impl: str = "auto"
    #: tensor parallelism (llm.multichip): tp > 1 shards the KV pool's
    #: head axis, attention heads and MLP weights over the first ``tp``
    #: devices (``parallel.mesh.make_tp_mesh``) — same engine semantics,
    #: same token stream (greedy/seeded), per-device HBM attribution on
    #: the ledger gauges. Requires n_heads % tp == 0 and d_ff % tp == 0.
    tp: int = 1
    spec_k: int = 0
    spec_drafter: str = "ngram"
    spec_ngram_max: int = 3
    spec_draft_ctx: int = 16
    spec_min_accept: float = 0.3
    spec_backoff_max: int = 32
    prefix_cache: bool = True
    prefix_cow_min_tokens: int = 1
    #: deadline-aware overload shedding (RESILIENCE.md): a submit carrying
    #: ``deadline_s`` is REJECTED with ``OverloadedError`` (429 at the
    #: proxy) when backlog ÷ observed service rate says the deadline
    #: cannot be met — queueing doomed work only steals KV blocks and
    #: decode slots from requests that could still make their deadlines.
    #: Requests without a deadline are never shed.
    shed: bool = True
    #: engine watchdog (llm.watchdog): stall-detection deadline and check
    #: cadence. The watchdog thread itself is started by the owner
    #: (serve.llm replicas start one; bare engines opt in via
    #: ``start_watchdog()``).
    watchdog_stall_s: float = 30.0
    watchdog_interval_s: float = 1.0


class LLMEngine:
    def __init__(
        self,
        model_cfg,
        params: dict,
        engine_cfg: Optional[EngineConfig] = None,
        draft_model_cfg=None,
        draft_params: Optional[dict] = None,
    ):
        self.cfg = engine_cfg or EngineConfig()
        self.model_cfg = model_cfg
        if self.cfg.spec_k < 0:
            raise ValueError("spec_k must be >= 0")
        if callable(params):
            # a caller that would hold nothing but a second reference hands
            # the tree over (``serve.llm``: ``[tree].pop``): this frame's is
            # then the only one, and the ``del params`` below (the paged
            # runners, which keep q / k / v in another form) lets go of the
            # given kernels BEFORE the pool is made beside them
            params = params()
        cache_cfg = CacheConfig(
            num_blocks=self.cfg.num_blocks,
            block_size=self.cfg.block_size,
            max_blocks_per_seq=self.cfg.max_blocks_per_seq,
        )
        cache_kind = getattr(model_cfg, "cache_kind", "kv")
        if cache_kind in ("hybrid", "paged", "windowed"):
            # a family that gives its own layer programs and the layout of
            # what its sequences hold: blocks ("paged": a KVBlockPool of the
            # body's layout, shared and forked as any), blocks AND a slot
            # of state behind one ledger ("hybrid": cache.HybridPool), or
            # blocks of two layer kinds, the window layers' handed back
            # behind the window ("windowed": cache.LayerTypedPool)
            self._refuse_for_hooks_body(state=cache_kind == "hybrid")
            from ray_tpu.llm.state_runner import HybridModelRunner

            self.runner = HybridModelRunner(model_cfg, params, self.cfg.block_size)
            body = self.runner.body
            if cache_kind == "hybrid":
                cache_cfg = HybridConfig(
                    self.cfg.num_blocks, self.cfg.block_size,
                    self.cfg.max_blocks_per_seq, self.cfg.max_slots,
                )
                self.pool = HybridPool(
                    cache_cfg, body.kv_layout(), body.state_leaves(self.cfg.block_size)
                )
            elif cache_kind == "windowed":
                layout = body.kv_layout()
                cache_cfg = LayerTypedConfig(
                    self.cfg.num_blocks, self.cfg.block_size, self.cfg.max_blocks_per_seq,
                    window=layout["window"], chunk=self.cfg.prefill_chunk,
                    slots=self.cfg.max_slots,
                )
                self.pool = LayerTypedPool(cache_cfg, layout)
            else:
                self.pool = KVBlockPool(cache_cfg, **body.kv_layout())
        elif cache_kind == "state":
            # a model without keys and values: a sequence owns one
            # fixed-size recurrent state (cache.StatePool), admission is a
            # free slot and the length limit is the model's positions
            self._refuse_for_hooks_body(state=True)
            from ray_tpu.llm.state_runner import StateModelRunner

            self.runner = StateModelRunner(model_cfg, params)
            cache_cfg = StateConfig(self.cfg.max_slots, model_cfg.seq_len)
            self.pool = StatePool(
                cache_cfg, model_cfg.n_layers, self.runner.body.state_shape,
                dtype=self.runner.body.state_dtype,
            )
        elif self.cfg.tp > 1:
            # tensor-parallel substrate (llm.multichip): sharded runner +
            # head-sharded pool over the same tp mesh; everything below
            # (scheduler, prefix cache, drafter, watchdog) is mesh-blind
            from ray_tpu.llm.multichip import (
                ShardedKVBlockPool,
                TensorParallelPagedModelRunner,
            )

            self.runner = TensorParallelPagedModelRunner(
                model_cfg, params, self.cfg.block_size,
                attn_impl=self.cfg.attn_impl, tp=self.cfg.tp,
            )
            del params
            self.pool = ShardedKVBlockPool(
                cache_cfg,
                n_layers=model_cfg.n_layers,
                n_heads=model_cfg.n_heads,
                head_dim=model_cfg.head_dim,
                dtype=model_cfg.dtype,
                tp=self.cfg.tp,
            )
        else:
            self.runner = PagedModelRunner(
                model_cfg, params, self.cfg.block_size, attn_impl=self.cfg.attn_impl
            )
            del params
            self.pool = KVBlockPool(
                cache_cfg,
                n_layers=model_cfg.n_layers,
                n_heads=model_cfg.n_heads,
                head_dim=model_cfg.head_dim,
                dtype=model_cfg.dtype,
            )
        self.prefix_cache = None
        if self.cfg.prefix_cache:
            from ray_tpu.llm.prefix_cache import PrefixCache

            self.prefix_cache = PrefixCache(
                self.pool, cow_min_tokens=self.cfg.prefix_cow_min_tokens
            )
        self.scheduler = Scheduler(
            self.pool, self.cfg.max_slots, prefix_cache=self.prefix_cache
        )
        self._drafter = None
        if self.cfg.spec_k > 0:
            from ray_tpu.llm.drafter import make_drafter

            self._drafter = make_drafter(
                self.cfg.spec_drafter,
                self.cfg.spec_k,
                self.cfg.max_slots,
                ngram_max=self.cfg.spec_ngram_max,
                draft_cfg=draft_model_cfg,
                draft_params=draft_params,
                draft_ctx=self.cfg.spec_draft_ctx,
            )
            # prefix-aware drafting: the n-gram drafter extends its
            # lookup past the local prompt into the shared radix paths —
            # a warm request's continuation often already sits on a path
            # another request prefilled (drafts affect throughput only;
            # verification keeps output exact either way)
            if self.prefix_cache is not None and hasattr(self._drafter, "corpus"):
                self._drafter.corpus = self.prefix_cache.paths
        # HBM ledger inputs fixed at init: params/drafter footprints never
        # change size (update_weights validates identical leaf shapes),
        # and the pool arrays are allocated once
        self._params_bytes = _tree_device_bytes(self.runner.params)
        self._drafter_bytes = _tree_device_bytes(
            getattr(self._drafter, "_params", None)
        )
        self._lock = threading.Lock()
        self._requests: dict[str, Request] = {}
        self._step_n = 0
        self._tokens_generated = 0
        self._flush = None  # a sink's ``flush_soon``, once ``_emit`` has pushed to one
        self._prefill_tokens = 0
        self._preemptions = 0
        self._finished_published = 0  # scheduler.finish_count already counted
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_draft_s = 0.0
        self._spec_skip = 0      # plain-decode steps left before re-probing
        self._spec_backoff = 0   # current backoff length (0 = speculating)
        # the step's own account (stats(): step_phase_s / loop / submit):
        # plain float adds under the lock the step or the submitter holds
        # anyway; _loop_idle_s alone is added to outside it, by the one
        # thread that runs run_loop
        self._phase_s = dict.fromkeys(STEP_PHASES, 0.0)
        self._step_wall_s = 0.0
        self._step_wall_hist = [0] * (len(STEP_WALL_BOUNDS_S) + 1)
        self._loop_lock_wait_s = 0.0
        self._loop_idle_s = 0.0
        self._submit_n = 0
        self._submit_lock_wait_s = 0.0
        self._submit_lock_wait_max_s = 0.0
        # decode / verify batches by the branch the sampler takes on the
        # device (models.sampling._draw_rows): no row sampled, no sort; else
        # the rows that sample and the width of the ladder they were sorted at
        self._sampler_steps = {
            "greedy_steps": 0, "sorted_steps": 0, "sorted_rows": 0, "sort_width_rows": 0,
        }
        # liveness beat, read LOCK-FREE by the watchdog and stream_tokens'
        # stall diagnosis (a wedged step holds the engine lock, so the
        # observers must never need it): (monotonic t of the last completed
        # step — idle ticks count, a wedge does not — , pending work then).
        # One-tuple assignment keeps the read torn-free under the GIL.
        self._beat: tuple[float, int] = (time.monotonic(), 0)
        self._watchdog = None
        # observed decode throughput (EWMA tokens/s) for deadline-aware
        # admission: backlog ÷ rate estimates a new request's completion
        self._rate = 0.0
        self._rate_mark = (time.monotonic(), 0)  # (t, tokens_generated)
        # learner→engine weight sync (rlhf.sync): monotonic version of the
        # params the jitted steps currently close over; update_weights
        # hot-swaps between step() iterations and every submit stamps the
        # version it was admitted under onto its Request
        self._weights_version = 0
        # model-length cap: paged table width, and the learned positional
        # table for GPT (rotary GPT-J has no absolute cap of its own)
        self.max_model_len = cache_cfg.max_seq_len
        if self.runner.arch == "gpt":
            self.max_model_len = min(self.max_model_len, model_cfg.seq_len)
        # slot state on the device (model_runner "Slot state"): the carry
        # every decode advances in place; the tables and knobs, each beside
        # the NumPy mirror that decides when to send them again; the two
        # operands of a step in which nothing joins and nothing changes
        S = self.cfg.max_slots
        place = self.runner.place
        self._carry = place(np.zeros((3, S), np.int32))
        self._no_patch = place(np.zeros((S, 4), np.int32))
        self._no_first = place(np.zeros(1, np.int32))
        tables = np.zeros((S, len(self.pool.table_row(None))), np.int32)
        knobs = pack_knobs(
            np.zeros(S), np.zeros(S), np.zeros(S), np.ones(S), np.zeros(S)
        )
        self._tables = (tables, place(tables))
        self._knobs = (knobs, place(knobs))
        # mirror of the carry: whose (position, counter) each slot feeds
        # next; None = unknown (a verify step ran past it): set every slot
        self._slot_ids: Optional[list] = [None] * S
        self._slot_next = np.zeros((2, S), np.int32)
        self._flight: Optional[_Flight] = None
        # (request, token, logprob): a final chunk's first token, on the device
        self._first: Optional[tuple] = None
        self._pipe = {
            "ahead_steps": 0, "serial_steps": 0, "drains": {},
            "discarded_tokens": 0,
            # chunks launched WITH the step's decode, as one program, and
            # alone, by a runner that offers that program (0 / 0 otherwise)
            "joint_steps": 0, "lone_chunks": 0,
            "uploads": {"full": 0, "none": 0, "partial": 0},
        }
        # a state pool's own account (stats()["state_pool"]): first chunks
        # that overwrote a slot's state, decodes launched and the live rows
        # they were sent (each row's state is read and written once a layer)
        # and the tokens of context those rows stood at (what an attention
        # over a sequence's own K/V reads); and the chunks launched, their
        # valid tokens and the tokens of context they attended (a chunk's
        # attention walks the sequence's K/V up to its own last token)
        self._state_n = {"overwrites": 0, "decodes": 0, "decode_rows": 0,
                         "decode_tokens": 0, "chunks": 0, "chunk_tokens": 0,
                         "chunk_context_tokens": 0}
        #: the tokens a pool with window layers lets a query see there (0:
        #: no such layers), and what its decodes' rows saw of them: the sum
        #: over live rows of min(context, window), beside ``decode_tokens``
        self._window = self.pool.cfg.window if isinstance(self.pool, LayerTypedPool) else 0
        self._decode_window_tokens = 0
        #: the pool of fixed-size states, where the model has one: the pool
        #: itself, or the part of a hybrid pool
        self._states = self.pool if isinstance(self.pool, StatePool) else getattr(
            self.pool, "states", None)

    def _refuse_for_hooks_body(self, state: bool) -> None:
        """What a family that brings its own layer programs cannot have.
        With a recurrent ``state`` beside or instead of blocks, what is built
        on shared K/V blocks means nothing, and snapshots of states, which
        would stand in for it, are later work.  Blocks alone are shared and
        forked as GPT-J's, so the prefix cache runs; the body has no verify
        program and no sharded form.  Say so instead of serving wrong
        tokens."""
        what = type(self.model_cfg).__name__
        holds = "no keys or values, only one recurrent state"
        if getattr(self.model_cfg, "cache_kind", "") == "hybrid":
            n = self.model_cfg.serving_body().kv_layout()["n_layers"]
            holds = ("a recurrent state beside "
                     + ("one layer's" if n == 1 else f"{n} layers'") + " keys and values")
        if self.cfg.prefix_cache and getattr(self.model_cfg, "cache_kind", "") == "windowed":
            raise ValueError(
                f"prefix_cache=True with {what}: the radix prefix cache keys "
                "blocks of ONE kind, and this model's window layers hand the "
                "blocks behind their window back (a shared prefix would need its "
                "window layers' last W tokens alone, kept beside the full "
                "layers' blocks: not implemented). "
                "Pass EngineConfig(prefix_cache=False)"
            )
        if self.cfg.prefix_cache and state:
            raise ValueError(
                f"prefix_cache=True with {what}: the radix prefix cache shares "
                f"blocks, and this model keeps {holds} per sequence; "
                "sharing a prefix would need "
                "state snapshots at block boundaries (not implemented). "
                "Pass EngineConfig(prefix_cache=False)"
            )
        if self.cfg.spec_k > 0:
            raise ValueError(
                f"spec_k={self.cfg.spec_k} with {what}: " + (
                    "verifying k drafted "
                    "tokens advances the recurrent state k steps and a rejection "
                    "would have to roll it back, which needs a state snapshot per "
                    "window (not implemented)." if state else
                    "its body gives a decode and a prefill-chunk program and no "
                    "verify program over a window of drafted tokens (not "
                    "implemented).") + " Pass EngineConfig(spec_k=0)"
            )
        if self.cfg.tp > 1:
            raise ValueError(
                f"tp={self.cfg.tp} with {what}: tensor parallelism here "
                "shards K/V heads and the paged kernels' pools; " + (
                    "the state "
                    "pool and the retention kernel have no sharded form yet."
                    if state else
                    "this body's pool has no head axis to shard and its layer "
                    "programs no sharded form yet (its experts are spread over "
                    "chips by expert parallelism, one share a chip).")
                + " Pass EngineConfig(tp=1)"
            )

    # -- public API --------------------------------------------------------

    def submit(
        self,
        prompt: list[int],
        params: Optional[SamplingParams] = None,
        deadline_s: Optional[float] = None,
        resume_tokens: tuple = (),
        sink=None,
    ) -> Request:
        """Queue a request; returns immediately (drive with ``step()`` or a
        loop thread; consume with ``stream_tokens``).

        ``sink`` — of a caller that took over its stream's sink
        (``_private.stream_sink.adopt``; the serve deployment): the tokens
        go to ``sink.push(token, t_emit)`` instead of ``req.stream``, the
        step hands every row's over with ONE ``sink.flush_soon()``
        (``_flush_streams``), and ``stream_tokens`` then yields nothing
        and returns at the request's end.

        ``resume_tokens`` — tokens a previous replica already generated for
        this request before dying (mid-stream failover, RESILIENCE.md).
        They pre-fold into the request's output: the cache is rebuilt by
        re-prefilling prompt + resumed tokens, generation continues at
        output index ``len(resume_tokens)`` with the same per-index PRNG
        keys, and only NEW tokens are streamed — token-identical to the
        unkilled run under greedy and seeded sampling alike.

        With a ``deadline_s`` and ``EngineConfig.shed`` on, admission is
        deadline-aware: when queue backlog ÷ observed service rate says the
        deadline cannot be met, the request is REJECTED with
        ``ray_tpu.exceptions.OverloadedError`` (``retry_after_s`` attached)
        instead of queued as doomed work.
        """
        params = params or SamplingParams()
        if params.max_tokens < 1:
            raise ValueError("max_tokens must be >= 1")
        if deadline_s is not None:
            import math

            # json.loads happily produces NaN/Infinity; a non-finite
            # deadline would make every "now >= deadline" reap check False
            # forever and poison the stream-timeout arithmetic downstream
            if not math.isfinite(deadline_s):
                raise ValueError(f"deadline_s must be finite, got {deadline_s}")
        if len(resume_tokens) > params.max_tokens:
            raise ValueError(
                f"resume_tokens ({len(resume_tokens)}) exceeds max_tokens "
                f"({params.max_tokens})"
            )
        total = len(prompt) + params.max_tokens
        if total > self.max_model_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_tokens ({params.max_tokens}) "
                f"exceeds max model length {self.max_model_len}"
            )
        # the request must be able to COMPLETE with the pool to itself —
        # admission's worst case is a re-admission one token before the end
        # plus one block of headroom (or, speculating, plus the window's k
        # provisional positions). Without this check an oversized request
        # passes validation, can never be admitted, and livelocks the FIFO
        # head (starving everything queued behind it).
        headroom = max(self.pool.cfg.block_size, self.cfg.spec_k)
        worst = min(total - 1 + headroom, self.pool.cfg.max_seq_len)
        usable = self.pool.cfg.num_blocks - 1
        if self.pool.blocks_for(worst) > usable:
            raise ValueError(
                f"request needs up to {self.pool.blocks_for(worst)} KV blocks "
                f"but the pool has only {usable} usable blocks "
                f"(num_blocks={self.pool.cfg.num_blocks}, block 0 reserved)"
            )
        deadline = time.time() + deadline_s if deadline_s is not None else None
        req = Request(
            prompt, params, deadline=deadline, resume_tokens=resume_tokens, sink=sink,
        )
        if req.phase_led is not None:
            # cross-process dispatch leg: the proxy's stream thread stamped
            # its dispatch anchor into the sampled trace-ctx dict it minted
            _phases.note_dispatch(req, _tracing.get_trace_context())
        # staleness stamp: the policy version this trajectory STARTS under
        # (a mid-generation hot-swap is fine — per-token behavior logprobs
        # stay exact regardless; the stamp drives the rlhf admission gate)
        req.weights_version = self._weights_version
        _events.record(
            "llm.submit", request_id=req.trace_id, engine_req=req.id,
            prompt_len=len(prompt), max_tokens=params.max_tokens,
            resumed=len(req.out),
        )
        # a resume that already satisfies its stopping condition finishes
        # without touching the scheduler: the previous replica died between
        # delivering the final token and the stream's "done" sentinel
        done_reason = None
        if req.out and req.out[-1] in params.stop_token_ids:
            done_reason = FINISH_STOP
        elif len(req.out) >= params.max_tokens:
            done_reason = FINISH_LENGTH
        if done_reason is not None:
            req.state = FINISHED
            req.finish_reason = done_reason
            if req.phase_led is not None:
                # fold the (near-empty) ledger so obs attribute still sees
                # this attempt — its whole life was the submit check
                now = time.time()
                _phases.charge(req.phase_led, _phases.QUEUE, now)
                _phases.fold_engine(req, now, done_reason)
            _events.record(
                "llm.finish", request_id=req.trace_id, engine_req=req.id,
                reason=done_reason, tokens_out=len(req.out),
            )
            req.stream.put(("done", done_reason))
            return req
        # step() holds this lock for a whole step and the loop takes it
        # again at once, so a submitter can wait for many steps: the two
        # stamps around the wait are the `lock` leg of the request's
        # ledger and stats()["submit"] alike
        t_wait = time.time()
        with _tracing.annotate("llm.submit.lock_wait"):
            self._lock.acquire()
        try:
            t_in = time.time()
            waited = max(0.0, t_in - t_wait)
            self._submit_n += 1
            self._submit_lock_wait_s += waited
            if waited > self._submit_lock_wait_max_s:
                self._submit_lock_wait_max_s = waited
            if req.phase_led is not None:
                _phases.charge(req.phase_led, _phases.LOCK, t_in)
            req.queued_t = t_in
            if self.cfg.shed and deadline_s is not None:
                est = self._estimate_completion_s_locked(
                    params.max_tokens - len(req.out)
                )
                if est is not None and est > deadline_s:
                    from ray_tpu.exceptions import OverloadedError

                    retry_after = max(0.1, round(est - deadline_s, 2))
                    _events.record(
                        "llm.shed", request_id=req.trace_id,
                        engine_req=req.id, estimate_s=round(est, 3),
                        deadline_s=deadline_s, retry_after_s=retry_after,
                    )
                    _metrics()["shed"].inc()
                    raise OverloadedError(
                        f"engine overloaded: estimated completion in "
                        f"{est:.2f}s exceeds the {deadline_s:.2f}s deadline "
                        f"(backlog at {self._rate:.1f} tokens/s)",
                        retry_after_s=retry_after,
                    )
            # re-stamp under the lock: a push that landed between Request
            # construction and admission is the version this trajectory
            # actually starts decoding under
            req.weights_version = self._weights_version
            self._requests[req.id] = req
            self.scheduler.add(req)
            # liveness beat: raise the pending count so the watchdog sees
            # the new work, and if the engine was IDLE until now, restart
            # the age clock — the stall timer must measure "work waited
            # this long", not "the engine was idle this long before work
            # arrived" (a stale timestamp here false-paged the stall SLO)
            t, prev_pending = self._beat
            self._beat = (
                time.monotonic() if prev_pending == 0 else t,
                self.scheduler.num_running + self.scheduler.num_waiting,
            )
        finally:
            self._lock.release()
        return req

    def _estimate_completion_s_locked(self, new_tokens: int) -> Optional[float]:
        """Seconds until a request needing ``new_tokens`` more tokens would
        finish, from the backlog of promised-but-ungenerated tokens and the
        observed service rate. None (no shedding evidence) when there is no
        backlog or no measured rate — an EMPTY engine never sheds, whatever
        a stale rate says (it will finish a lone request as fast as it can;
        the estimate only means something when the request must wait its
        turn behind real work that keeps the rate sample fresh)."""
        rate = self._rate
        if rate <= 1e-6:
            return None
        backlog = sum(
            max(r.params.max_tokens - len(r.out), 0)
            for r in list(self.scheduler.waiting) + self.scheduler.running
        )
        if backlog <= 0:
            return None
        return (backlog + new_tokens) / rate

    def cancel(self, req_id: str) -> bool:
        """Flag a request for cancellation; the next step reaps it (frees
        its slot and blocks, ends its stream)."""
        req = self._requests.get(req_id)
        if req is None:
            return False
        req.cancelled.set()
        return True

    def has_work(self) -> bool:
        """Requests queued or running, or a decode still to be read."""
        with self._lock:
            return self.scheduler.has_work() or self._flight is not None

    @property
    def weights_version(self) -> int:
        """Version of the params the engine currently decodes with."""
        return self._weights_version

    def update_weights(self, params: dict, version: Optional[int] = None) -> int:
        """Hot-swap the model parameters between step() iterations WITHOUT
        draining in-flight requests (the rlhf learner→engine sync path;
        ``rlhf.sync.apply_weight_update`` wraps this for chunked
        object-plane pushes).

        The new pytree must match the structure and leaf shapes of the
        tree the engine was BUILT from (``runner.given``; the resident one
        may keep it in another form); leaves arrive in any float dtype (a
        learner pushes its fp32 masters) and are cast to the dtype the
        engine was given — then the
        jitted step functions never retrace (they cache on shape and
        dtype, and params are a traced argument, not a captured
        constant). Leaves are ``device_put`` once here so steady-state
        steps don't re-upload host arrays every call. The decode in flight
        is read and emitted first (a token sampled under the old weights
        is on the host, in ``req.out``, before the version moves); in-flight
        requests then continue under the new weights from their next step —
        exactly the semantics async RL wants (and their per-token behavior
        logprobs were captured at sample time, so off-policy correction
        stays exact across the swap).

        ``version`` must be monotonically increasing (default: current+1).
        Returns the installed version.
        """
        import jax

        # what the runner was GIVEN, abstractly: its resident tree may hold
        # the same weights in another form (q / k / v as one leaf), which
        # prepare_params below makes of the new tree as it did of the first
        resident = self.runner.given  # structure/shapes/dtypes never change
        old_struct = jax.tree_util.tree_structure(resident)
        new_struct = jax.tree_util.tree_structure(params)
        if old_struct != new_struct:
            raise ValueError(
                "update_weights pytree structure mismatch: "
                f"{new_struct} != {old_struct}"
            )
        for a, b in zip(
            jax.tree_util.tree_leaves(resident), jax.tree_util.tree_leaves(params)
        ):
            if a.shape != b.shape:
                raise ValueError(
                    f"update_weights leaf mismatch: {b.shape} != {a.shape} "
                    "(a retrace mid-traffic is never acceptable)"
                )
        params = jax.tree_util.tree_map(
            lambda new, cur: new if new.dtype == cur.dtype else new.astype(cur.dtype),
            params, resident,
        )
        # prepare_params owns form and placement: device conversion and the
        # packed q / k / v on one chip, their column shards under tp>1 —
        # either way the swap lands with the compiled steps' exact layout
        new = self.runner.prepare_params(params)
        t0 = time.perf_counter()
        with self._lock:
            if version is None:
                version = self._weights_version + 1
            if version < self._weights_version:
                raise ValueError(
                    f"weights_version must not go backwards: "
                    f"{version} < {self._weights_version}"
                )
            self._drain("update_weights")
            self.runner.params = new
            self._weights_version = version
            # cached prefix KV was computed under the OLD weights: flush
            # the tree so no new request seeds from it (in-flight
            # requests keep their own references — same mid-swap
            # semantics as their continued decode under new weights)
            if self.prefix_cache is not None:
                self.prefix_cache.flush(reason="weights_update")
            in_flight = self.scheduler.num_running + self.scheduler.num_waiting
        _events.record(
            "llm.weights_update", version=version,
            apply_s=round(time.perf_counter() - t0, 6), in_flight=in_flight,
        )
        return version

    def stream_tokens(self, req: Request, timeout: float = 60.0) -> Iterator[int]:
        """Yield the request's tokens as the engine produces them.

        A timeout raises ``EngineStalledError`` (a ``TimeoutError``
        subclass) carrying the stall diagnosis — last-step age, queue
        depth, and KV utilization — gathered WITHOUT the engine lock, so
        the diagnosis works precisely when the step loop is wedged holding
        it."""
        import queue as _q

        wake = _stream_stats.stations().wake
        wait = timeout
        while True:
            try:
                item = req.stream.get(timeout=wait)
            except _q.Empty:
                last = req.last_token_t
                if req.sink is not None and last is not None:
                    # its tokens go to its sink and none passes here: the
                    # stall is ``timeout`` without one, counted from the last
                    wait = timeout - (time.perf_counter() - last)
                    if wait > 0:
                        continue
                from ray_tpu.llm.watchdog import EngineStalledError

                age, pending = self.progress()
                kv = self.pool.utilization()
                _events.record(
                    "llm.watchdog.stall", request_id=req.trace_id,
                    engine_req=req.id, source="stream_tokens",
                    last_step_age_s=round(age, 3), queue_depth=pending,
                    kv_utilization=round(kv, 4), timeout_s=timeout,
                )
                raise EngineStalledError(
                    f"no token from {req.id} within {timeout}s "
                    f"(state={req.state})",
                    last_step_age_s=age,
                    queue_depth=pending,
                    kv_utilization=kv,
                ) from None
            if item[0] == "token":
                # the `wake` leg: how long the token lay in the queue
                # before this handler thread ran (one process, one clock)
                wake.observe(time.perf_counter() - item[2])
                yield item[1]
            else:
                return

    def progress(self) -> tuple[float, int]:
        """(seconds since the last completed step tick, pending work at
        that tick) — lock-free, safe to call while a step is wedged."""
        t, pending = self._beat
        return time.monotonic() - t, pending

    def start_watchdog(self):
        """Start (once) the engine watchdog thread — stall detection,
        deadline/cancel reaping that works around a wedged step loop, and
        the KV-pool leak audit (``llm.watchdog`` module doc). Serve
        replicas call this; bare engines may too."""
        if self._watchdog is None:
            from ray_tpu.llm.watchdog import EngineWatchdog

            self._watchdog = EngineWatchdog(
                self,
                stall_deadline_s=self.cfg.watchdog_stall_s,
                interval_s=self.cfg.watchdog_interval_s,
            ).start()
        return self._watchdog

    def generate(
        self,
        prompt: list[int],
        params: Optional[SamplingParams] = None,
        deadline_s: Optional[float] = None,
    ) -> list[int]:
        """Blocking convenience: submit and drive until finished. Safe to
        call while a loop thread is also stepping (steps serialize)."""
        req = self.submit(prompt, params, deadline_s)
        while not req.finished:
            if not self.step():
                time.sleep(0.001)
        return list(req.out)

    def warmup(self) -> None:
        """Compile every jitted step path — prefill, decode, the two as one
        program where the runner offers it, and (when speculating) verify —
        so the first real request runs at steady-state latency.  A
        speculating engine routes decode through ``verify_step`` until
        acceptance drops, so one generate would leave the PLAIN decode path
        (the backoff fallback) cold.  The
        verify jit is driven DIRECTLY with a dummy batch rather than via
        generate: whether a generate ever reaches verification is gated
        on the drafter finding a confident match in the (model-dependent)
        warmup output, so only a direct call guarantees the compile.  The
        dummy batch's all-zero block tables route every provisional write
        to the reserved trash block — real pool contents are untouched."""
        if self._joint is not None and self._drafter is None:
            # the program of a step that carries a chunk needs a chunk AND
            # a decoding row, which a lone generate never has: a second
            # request arrives while the first decodes, and its chunk rides
            # (the engine's own builders pack the operands, as in service)
            first = self.submit([0], SamplingParams(max_tokens=4))
            self.step()  # its chunk alone; its first decode launched
            second = self.submit([0], SamplingParams(max_tokens=2))
            while not (first.finished and second.finished):
                if not self.step():
                    time.sleep(0.001)
        else:
            self.generate([0], SamplingParams(max_tokens=2))
        if self.prefix_cache is not None:
            # compile the CoW fork jit with trash→trash lanes (block 0
            # copied onto itself: identity, real pool contents untouched)
            with self._lock:
                z = np.zeros(self.cfg.max_slots, np.int32)
                self.pool.arrays = self.runner.fork_blocks(*self.pool.arrays, z, z)
        if self._drafter is not None:
            with self._lock:
                self._spec_skip = 1 << 30  # force the plain-decode path
            self.generate([0], SamplingParams(max_tokens=2))
            with self._lock:
                self._spec_skip = 0
                self._spec_backoff = 0
                S, W = self.cfg.max_slots, self.cfg.spec_k + 1
                *self.pool.arrays, _, _, _ = self.runner.verify_step(
                    *self.pool.arrays,
                    np.zeros((S, W), np.int32),
                    np.zeros(S, np.int32),
                    np.zeros((S, self.pool.cfg.max_blocks_per_seq), np.int32),
                    np.zeros(S, np.float32),
                    np.zeros(S, np.int32),
                    np.ones(S, np.float32),
                    np.zeros(S, np.uint32),
                    np.zeros(S, np.int32),
                )

    def stats(self) -> dict:
        with self._lock:
            # ONE pool-ledger snapshot feeds utilization, free_blocks and
            # the hbm section — three separate property reads could each
            # interleave with an allocation and disagree in one response
            led = self.hbm_ledger()
            s = {
                "running": self.scheduler.num_running,
                "waiting": self.scheduler.num_waiting,
                "queue_depth": self.scheduler.num_waiting,
                "kv_utilization": led["utilization"],
                "free_blocks": led["free_blocks"],
                "steps": self._step_n,
                "tokens_generated": self._tokens_generated,
                "prefill_tokens_computed": self._prefill_tokens,
                "preemptions": self._preemptions,
                "service_rate_tokens_per_s": self._rate,
                "weights_version": self._weights_version,
                # wall time of THIS reading, taken under the lock: a caller
                # can wait many steps for it, and a rate over two readings
                # divides by the difference of these, not of the asking
                "t_read": time.time(),
                # the loop's wall time, split three ways (seconds, since
                # start): inside steps that had work, waiting for the lock
                # a submitter or a reader held, idle with nothing to do
                "loop": {
                    "step_wall_s": self._step_wall_s,
                    "lock_wait_s": self._loop_lock_wait_s,
                    "idle_s": self._loop_idle_s,
                    "step_wall_hist": list(self._step_wall_hist),
                    "step_wall_bounds_s": list(STEP_WALL_BOUNDS_S),
                },
                "step_phase_s": dict(self._phase_s),
                "submit": {
                    "n": self._submit_n,
                    "lock_wait_s": self._submit_lock_wait_s,
                    "lock_wait_max_s": self._submit_lock_wait_max_s,
                },
                "queue": {
                    "admitted": self.scheduler.admit_count,
                    "wait_s": self.scheduler.queue_wait_s,
                },
                "sampler": dict(self._sampler_steps),
                # the decode pipeline: launches made with the step before
                # still unread / with nothing in flight; out-of-turn reads
                # by reason; tokens dropped after a stop token; what each
                # launch sent of (patch, tables, knobs); and the rows of
                # the decode in flight NOW, whose tokens no counter above
                # holds yet
                "pipeline": dict(
                    self._pipe,
                    drains=dict(self._pipe["drains"]),
                    uploads=dict(self._pipe["uploads"]),
                    in_flight=len(self._flight.rows) if self._flight else 0,
                ),
            }
            if self.prefix_cache is not None:
                s["prefix_cache"] = self.prefix_cache.stats()
            if self._states is not None:
                st = self._states
                s["state_pool"] = dict(
                    self._state_n, slots=st.cfg.slots, live=st.num_used_blocks,
                    bytes=st.device_bytes, kinds=st.leaf_bytes(),
                )
                if st is not self.pool:  # a hybrid pool: its K/V layers
                    s["kv_pool"] = self._kv_pool_stats(led)
            elif self.runner.arch == "hybrid":
                # a body over blocks alone
                s["kv_pool"] = self._kv_pool_stats(led)
                if self._window:
                    # blocks of two layer kinds (cache.LayerTypedPool): what
                    # the sequences hold of each now, what the window layers
                    # handed back, and the tokens the decodes' rows saw in a
                    # window layer.  The window sub-pool is what a slot holds
                    # at most, as a state is: the steps' own account stands
                    # under ``state_pool`` too, where the readers of a
                    # decode's and a chunk's occupancy look for it
                    win = self.pool.windowed
                    s["kv_pool"].update(
                        self.pool.stats(), window=self._window,
                        decode_window_tokens=self._decode_window_tokens)
                    s["state_pool"] = dict(
                        self._state_n, slots=self.cfg.max_slots,
                        live=self.scheduler.num_running, bytes=win.device_bytes,
                        kinds={"window_kv": win.device_bytes},
                        decode_window_tokens=self._decode_window_tokens,
                    )
            # what the body counted on the device: the reader is taken here
            # and called below, outside the lock, because it waits for every
            # step launched so far and the step loop must not wait with it
            counted = self.runner.counters() if self.runner.arch == "hybrid" else dict
            s["hbm"] = led
            s["retraces"] = self.runner.prof.retraces
            s["tp"] = self.cfg.tp
            if self.cfg.tp > 1:
                s["tp_sum"] = self.runner.tp_sum_stats()
            if self._drafter is not None:
                s["spec_proposed"] = self._spec_proposed
                s["spec_accepted"] = self._spec_accepted
                s["spec_acceptance_rate"] = self._spec_accepted / max(
                    self._spec_proposed, 1
                )
                s["spec_draft_seconds"] = self._spec_draft_s
        s.update(counted())
        return s

    def _kv_pool_stats(self, led: dict) -> dict:
        """The paged part of a hooks body's pool (``stats()["kv_pool"]``)."""
        return {
            "blocks": self.pool.cfg.num_blocks - 1,
            "live": led["seq_bytes"] // led["block_bytes"],
            "block_tokens": self.pool.cfg.block_size,
            "bytes": led["pool_bytes"] - led.get("state_bytes", 0),
            # the decodes' occupancy, the same three counts ``state_pool``
            # has where there is one: a reader of either finds them
            **{k: self._state_n[k] for k in ("decodes", "decode_rows", "decode_tokens")},
        }

    def device_report(self) -> dict:
        """``util.device_prof.device_report()`` for the engine's process
        plus what the engine put on the device: the attention dispatch
        rule's answer for this pool next to the names of the Mosaic
        kernels actually inside each jitted step called so far, each
        step's first-call seconds and their split into trace, lower,
        compile-or-cache-load and run (``StepRunner.first_call``), the
        jit-cache sizes the retrace detector reads, and the HBM ledger."""
        from ray_tpu.ops.paged_attention import auto_impl
        from ray_tpu.util.device_prof import device_report

        rep = device_report()
        with self._lock:
            rep["first_call_s"] = dict(self.runner.first_call_s)
            rep["first_call"] = {k: dict(v) for k, v in self.runner.first_call.items()}
            rep["jit_sites"] = self.runner.prof.stats()
            rep["hbm"] = self.hbm_ledger()
        rep["attention"] = {
            "configured": self.cfg.attn_impl,
            # the paged kernels' dispatch rule; a state model has none
            # (by the width of a head AS THE POOL HOLDS IT: a pair of
            # differential heads is one)
            "auto_rule": auto_impl(
                self.pool.cfg.block_size, self.pool.k.shape[-1]
            ) if self.pool.paged else None,
            # lowering takes seconds at full depth: outside the lock
            "mosaic_kernels": self.runner.kernels_in_steps(),
        }
        return rep

    def run_loop(self, stop: threading.Event, idle_sleep_s: float = 0.002) -> None:
        """Drive ``step()`` until ``stop`` is set (serve replicas run this
        in a daemon thread)."""
        while not stop.is_set():
            if not self.step():
                t0 = time.perf_counter()
                with _tracing.annotate("llm.loop.idle"):
                    stop.wait(idle_sleep_s)
                self._loop_idle_s += time.perf_counter() - t0
        with self._lock:
            self._drain("stop")

    # -- the step ----------------------------------------------------------

    def _phase(self, key: str, span: Optional[str] = None) -> _Phase:
        """``with self._phase("admit"):`` — the span ``llm.step.admit`` on
        the profiler's clock and the seconds into ``step_phase_s["admit"]``
        (``span`` where the two differ: the verify path)."""
        return _Phase(self._phase_s, key, "llm.step." + (span or key))

    def step(self) -> bool:
        """One engine iteration; returns True when any work was done."""
        t_wait = time.perf_counter()
        with _tracing.annotate("llm.loop.lock_wait"):
            self._lock.acquire()
        try:
            t_in = time.perf_counter()
            self._loop_lock_wait_s += t_in - t_wait
            sched = self.scheduler
            if not sched.has_work() and self._flight is None:
                self._publish_gauges()
                self._beat = (time.monotonic(), 0)
                self._loop_idle_s += time.perf_counter() - t_in
                return False
            self._step_n += 1
            m = _metrics()
            m["steps"].inc()
            # spec stats fill in during the step; span attributes serialize
            # at span EXIT, so the dict lands populated in the trace
            spec_info: dict = {}
            attrs = dict(
                step=self._step_n,
                running=sched.num_running,
                waiting=sched.num_waiting,
            )
            with _tracing.annotate("llm.step", **attrs):
                if self._drafter is not None:
                    attrs["spec"] = spec_info
                with _tracing.span("llm_engine_step", **attrs):
                    with self._phase("admit"):
                        doomed = self._doomed()
                        reason = self._doomed_in_flight(doomed)
                        if reason is None:
                            self._admit(doomed)
                    if reason is not None:
                        self._drain(reason)
                        with self._phase("admit"):
                            self._admit(doomed)
                    with self._phase("prefill_build"):
                        rides = self._chunk_rides()
                    did = False if rides else self._prefill_one()
                    if self._drafter is not None and self._spec_skip == 0:
                        did = self._spec_decode_all(spec_info) or did
                    else:
                        did_decode = self._decode_all(chunk=rides)
                        if did_decode and self._spec_skip > 0:
                            self._spec_skip -= 1  # backoff ticks on real decodes
                        did = did_decode or did
                with self._phase("publish"):
                    # prune finished requests: the registry otherwise retains
                    # every Request (prompt, output, stream queue) for the
                    # replica's lifetime. Callers keep their own Request
                    # references; cancel() of a pruned id is a no-op, which
                    # is correct for finished work.
                    self._requests = {
                        k: r for k, r in self._requests.items() if not r.finished
                    }
                    self._flush_streams()  # the speculative emit, the admit's reap
                    self._publish_gauges()
            self._beat = (
                time.monotonic(), sched.num_running + sched.num_waiting
            )
            wall = time.perf_counter() - t_in
            self._step_wall_s += wall
            self._step_wall_hist[bisect.bisect_left(STEP_WALL_BOUNDS_S, wall)] += 1
            return did or sched.has_work() or self._flight is not None
        finally:
            self._lock.release()

    # -- internals (all called under the lock) -----------------------------

    def _reap(self) -> int:
        """Finish cancelled and deadline-blown requests (lock held). Also
        the watchdog's locked reap path — ONE copy of the doomed-request
        predicate. Returns how many were finished."""
        doomed = self._doomed()
        reason = self._doomed_in_flight(doomed)
        if reason is not None:
            self._drain(reason)
        n = self._finish_doomed(doomed)
        self._flush_streams()  # the watchdog's call is followed by no step
        return n

    def _doomed(self) -> list:
        """(request, finish reason) of what the reap is about to finish."""
        now = time.time()
        doomed = []
        for req in list(self.scheduler.waiting) + self.scheduler.running:
            if req.cancelled.is_set():
                doomed.append((req, FINISH_CANCELLED))
            elif req.deadline is not None and now >= req.deadline:
                doomed.append((req, FINISH_DEADLINE))
        return doomed

    def _doomed_in_flight(self, doomed: list) -> Optional[str]:
        """The finish reason of a doomed row with a token in flight: the
        device is read dry under it first, so that the token is in
        ``req.out`` and never dropped in silence.  None: nothing to read."""
        for req, reason in doomed:
            if self._ahead(req):
                return reason
        return None

    def _finish_doomed(self, doomed: list) -> int:
        n = 0
        for req, reason in doomed:
            if not req.finished:  # the drained token may have ended it
                self.scheduler.finish(req, reason)
                n += 1
        return n

    def _admit(self, doomed: list) -> None:
        """The admit phase's work once nothing doomed is still in flight."""
        self._finish_doomed(doomed)
        self.scheduler.admit()
        self._apply_cow()

    def _apply_cow(self) -> None:
        """Drain the scheduler's queued copy-on-write forks (cache-aware
        admissions that diverged inside a cached block): one batched
        device copy duplicates each src block into the request's fresh
        dst block BEFORE any prefill chunk attends through it."""
        pend = self.scheduler.pending_cow
        if not pend:
            return
        self.scheduler.pending_cow = []
        F = self.cfg.max_slots
        for start in range(0, len(pend), F):
            batch = pend[start : start + F]
            src = np.zeros(F, np.int32)
            dst = np.zeros(F, np.int32)
            for j, (s, d, _rid) in enumerate(batch):
                src[j], dst[j] = s, d
            self.pool.arrays = self.runner.fork_blocks(*self.pool.arrays, src, dst)
        now = time.time()
        for _s, _d, rid in pend:
            req = self._requests.get(rid)
            if req is not None and req.phase_led is not None:
                _phases.charge(req.phase_led, _phases.COW_FORK, now)

    @property
    def _joint(self):
        """The runner's ONE program for a chunk and the decode rows
        (``PagedModelRunner.prefill_with_slots``), or None where it offers
        none: the tensor-parallel runner, a hooks-body runner."""
        return getattr(self.runner, "prefill_with_slots", None)

    def _next_prefill(self) -> Optional[Request]:
        """The oldest admission still prefilling."""
        pre = [
            r for r in self.scheduler.slots if r is not None and r.state == PREFILL
        ]
        if not pre:
            return None
        return min(pre, key=lambda r: self.scheduler._admitted_at.get(r.id, 0))

    def _chunk_rides(self) -> bool:
        """Whether this step's chunk goes out WITH its decode, as one
        program (``model_runner`` ``_prefill_with_slots_impl``: a layer's
        weights fetched once for both).  By what the engine observes: the
        runner offers the program, a sequence is prefilling and rows decode;
        a drafter reads every token on the host at once and keeps two
        launches."""
        return (
            self._drafter is None
            and self._joint is not None
            and self._next_prefill() is not None
            and bool(self._decode_rows())
        )

    def _build_chunk(self) -> Optional[tuple]:
        """One chunk for the oldest admission still prefilling: (request,
        valid tokens, whether it is the prompt's last, the chunk's operands
        after the pools), or None when nothing prefills."""
        with self._phase("prefill_build"):
            req = self._next_prefill()
            if req is None:
                return None
            chunk = self.cfg.prefill_chunk
            # a preempted request replays prompt + already-generated tokens
            # to rebuild its cache; a fresh one just prefills its prompt —
            # and a prefix-cache hit starts past the matched prefix either way
            full = req.prompt + req.out
            piece = full[req.prefill_pos : req.prefill_pos + chunk]
            n_valid = len(piece)
            tokens = np.zeros(chunk, np.int32)
            tokens[:n_valid] = piece
            if self._window:
                # the window layers' blocks follow the chunks (cache.
                # LayerTypedPool: the full layers' were claimed at admission)
                self.pool.slide(req.id, req.prefill_pos, n_valid)
            table = self.pool.table_row(req.id)
            # the program samples from every chunk's last logits; only the
            # final chunk's token is kept, so only it pays for its knobs
            final = req.prefill_pos + n_valid >= len(full)
            p = req.params
            sampling = pack_knobs(
                len(req.out), p.temperature if final else 0.0, p.top_k, p.top_p,
                p.seed,
            )
            return req, n_valid, final, (tokens, req.prefill_pos, n_valid, table, sampling)

    def _chunk_launched(self, req: Request, n_valid: int, final: bool, tok, logp):
        """The host's book-keeping for a chunk in flight, billed to
        ``prefill_build`` like the work before the launch.  Returns the
        ``(request, token, logprob)`` a FINAL chunk left on the device."""
        with self._phase("prefill_build"):
            if req.prefill_pos == 0:
                self._state_n["overwrites"] += 1  # read by a state pool only
            req.prefill_pos += n_valid
            self._prefill_tokens += n_valid
            self._state_n["chunks"] += 1
            self._state_n["chunk_tokens"] += n_valid
            self._state_n["chunk_context_tokens"] += req.prefill_pos
            if req.phase_led is not None:
                # a recompute's re-prefill is preemption cost, not prefill
                _phases.charge(
                    req.phase_led,
                    _phases.PREEMPT if req.phase_recompute else _phases.PREFILL,
                    time.time(),
                )
            _metrics()["prefill_tokens"].inc(n_valid)
            _events.record(
                "llm.prefill_chunk", request_id=req.trace_id, engine_req=req.id,
                pos=req.prefill_pos, of=len(req.prompt) + len(req.out), n=n_valid,
            )
            if self.prefix_cache is not None:
                # register the now-complete PROMPT blocks (generated tokens
                # never enter the tree — only prompt content is matchable);
                # the admission epoch keeps a request whose prefill straddled
                # a weight-swap flush from re-inserting old-weight KV
                self.prefix_cache.insert(
                    req.prompt,
                    self.pool.blocks_of(req.id),
                    limit=min(req.prefill_pos, len(req.prompt)),
                    epoch=req.cache_epoch,
                )
            if not final:
                return None
            req.state = RUNNING
            req.phase_recompute = False  # recompute ends where decode resumes
            return req, tok, logp

    def _prefill_one(self) -> bool:
        """One chunk in a launch of its own: into an empty batch, under a
        drafter, on a runner with no joint program."""
        built = self._build_chunk()
        if built is None:
            return False
        if self._first is not None:
            # the first token a final chunk left a step ago beside a decode
            # (``_launch_decode``), of a row that decodes no further: out,
            # before this chunk's takes its place
            self._drain("lone_chunk")
        req, n_valid, final, operands = built
        with self._phase("prefill_launch"):
            *arrays, _logits, tok, logp = self.runner.prefill_chunk(
                *self.pool.arrays, *operands
            )
            self.pool.arrays = arrays
        if self._joint is not None:
            self._pipe["lone_chunks"] += 1
        # the first generated token is on the device: the row can decode in
        # THIS step (PATCH_JOIN) and the token is read once that decode is
        # launched, not before
        self._first = self._chunk_launched(req, n_valid, final, tok, logp)
        if final and self._drafter is not None:
            self._read_first()  # a drafter reads the host's tokens: wait now
        return True

    def _grow_all(self, rows: list) -> None:
        """Ensure every ``(request, extra)`` has cache room for the
        position the next step writes (``seq_len - 1`` plus ``extra``: its
        tokens in flight, or a window's provisional positions), evicting
        the youngest when the pool is dry, with preemption accounting."""
        sched = self.scheduler
        for req, extra in rows:
            if req.state != RUNNING:
                continue
            before = sched.preempt_count
            if not sched.grow_for_decode(req, extra=extra):
                pass  # req itself was preempted; it re-prefills later
            self._preemptions += sched.preempt_count - before
            _metrics()["preempt"].inc(sched.preempt_count - before)

    def _note_sampler(self, temp: np.ndarray, window: int = 1) -> None:
        """Count the batch about to be launched by the sampler's own rule
        (``sort_rung`` of the rows with ``temp > 0``; empty slots carry 0;
        a verify batch has ``window`` rows a slot): how often the all-greedy
        skip engages, how many rows sampled and how wide the sort that drew
        them was, in ``stats()["sampler"]``."""
        n, b = int((temp > 0.0).sum()) * window, temp.size * window
        s = self._sampler_steps
        if n == 0:
            s["greedy_steps"] += 1
            return
        s["sorted_steps"] += 1
        s["sorted_rows"] += n
        s["sort_width_rows"] += sort_ladder(b)[sort_rung(n, b)]

    # -- the decode in flight ------------------------------------------------

    def _ahead(self, req: Request) -> int:
        """Tokens of ``req`` sampled on the device and not read yet."""
        n = 1 if self._first is not None and self._first[0] is req else 0
        if self._flight is not None and req.id in self._flight.ids:
            n += 1
        return n

    def _take_flight(self) -> tuple:
        """The decode in flight off the device: a step's ONE wait for a
        decode.  Returns it with its tokens and logprobs on the host."""
        import jax

        flight, self._flight = self._flight, None
        # under the engine's lock whoever calls (the step; a weight swap or
        # the loop's stop reading the device dry): the one unbounded daemon
        # acquirer is the step loop, which IS this read; the watchdog takes
        # the lock with a timeout and diagnoses from the lock-free beat
        return flight, *jax.device_get((flight.nxt, flight.logp))  # raylint: disable=RL011

    def _emit_flight(self, flight: _Flight, nxt, logp) -> None:
        # a row that ended on a stop token a step ago was still in this
        # launch: its extra token goes nowhere
        rows = [(i, r) for i, r in flight.rows if not r.finished]
        self._pipe["discarded_tokens"] += len(flight.rows) - len(rows)
        now = time.time()
        for i, req in rows:
            if req.phase_led is not None:
                _phases.charge(req.phase_led, _phases.DECODE, now)
        for i, req in rows:
            _events.record(
                "llm.decode", request_id=req.trace_id, engine_req=req.id,
                step=flight.step, token=int(nxt[i]),
            )
            self._emit(req, int(nxt[i]), float(logp[i]))
        _metrics()["tokens_per_step"].set(len(rows))

    def _take_first(self) -> tuple:
        """A final chunk's first token off the device: waits for the chunk.
        Returns (request, token, logprob)."""
        import jax

        (req, tok, logp), self._first = self._first, None
        tok, logp = jax.device_get((tok, logp))  # raylint: disable=RL011 (as _take_flight)
        return req, int(tok[0]), float(logp[0])

    def _read_first(self) -> None:
        """Wait for the first token and emit it.  Its own wait, AFTER the
        decode's tokens are out: read in one go with them, every row's
        token would wait for the chunk too, and a gap would hold the chunk
        before the decode and the one after it."""
        with self._phase("prefill_sample"):
            got = self._take_first()
        with self._phase("emit"):
            self._emit(*got)
            self._flush_streams()

    def _flush_streams(self) -> None:
        """Hand what ``_emit`` pushed since the last call to the worker's
        ONE sender thread (``_private.stream_sink.Outbox.flush_soon``): it
        leaves as one message for every row's token.  This thread only
        wakes the sender: under its lock and between two launches it
        serializes nothing and touches no connection.  Called once after a
        step's emit, after the emits out of turn (``_read_first``,
        ``_drain``, the reap) and at the step's end; nothing pushed,
        nothing done.  A stream's end cannot overtake its last tokens: the
        worker sends what a sink still holds before the completion
        (``Sink.close``)."""
        flush, self._flush = self._flush, None
        if flush is not None:
            flush()

    def _drain(self, reason: str) -> bool:
        """Read and emit whatever is still on the device, out of turn:
        before anything that must see every token on the host."""
        if self._flight is None and self._first is None:
            return False
        with self._phase("drain"):
            if self._flight is not None:
                self._emit_flight(*self._take_flight())
            if self._first is not None:
                self._emit(*self._take_first())
            self._flush_streams()
        drains = self._pipe["drains"]
        drains[reason] = drains.get(reason, 0) + 1
        return True

    def _decode_rows(self) -> list:
        """``(slot, request, tokens in flight)`` of the rows the next
        decode samples for: RUNNING, and not already past their last token
        with what is in flight."""
        rows = []
        for i, r in enumerate(self.scheduler.slots):
            if r is None or r.state != RUNNING:
                continue
            ahead = self._ahead(r)
            if (
                len(r.out) + ahead < r.params.max_tokens
                and r.seq_len + ahead < self.max_model_len
            ):
                rows.append((i, r, ahead))
        return rows

    def _would_preempt(self, rows: list) -> bool:
        """Whether growing ``rows`` by a position each takes more blocks
        than the free list and the prefix tree can give."""
        pool = self.pool
        need = sum(
            max(
                0,
                pool.blocks_for(min(r.seq_len + ahead, pool.cfg.max_seq_len))
                - len(pool.blocks_of(r.id)),
            )
            for _, r, ahead in rows
        )
        free = pool.num_free_blocks
        return need > free and need > free + pool.num_evictable_blocks

    def _ready_decode(self) -> Optional[tuple]:
        """``_build_decode`` over every row that has a token to sample, the
        device read dry first where growing them would preempt."""
        with self._phase("decode_build"):
            rows = self._decode_rows()
            # a row about to be preempted replays prompt + out: its token in
            # flight has to be in out first
            blocked = (
                self._flight is not None or self._first is not None
            ) and self._would_preempt(rows)
            built = None if blocked else self._build_decode(rows)
        if blocked:
            self._drain("preempt")
            with self._phase("decode_build"):
                built = self._build_decode(self._decode_rows())
        return built

    def _launch_decode(self, chunk: bool = False) -> Optional[_Flight]:
        """Build and launch one batched decode over every row that has a
        token to sample; None when there is none.  With ``chunk``
        (``_chunk_rides``) the step's prefill chunk goes out in the SAME
        program.  A final chunk's first token then leaves the device beside
        this launch's tokens, so its row cannot decode in this pass: it
        joins the NEXT launch (``PATCH_JOIN``, still no host round trip) and
        the token is read where this flight is read (``_Flight.first``)."""
        built = self._ready_decode()
        if chunk and built is None:
            # growing the rows preempted every one of them: the chunk goes
            # alone, as into an empty batch, and a final chunk's row decodes
            self._prefill_one()
            built, chunk = self._ready_decode(), False
        if built is None:
            return None
        rows, first_tok, patch = built
        # the decode is built FIRST: growing its rows may have preempted the
        # sequence the chunk was for (the youngest), whose blocks are gone
        piece = self._build_chunk() if chunk else None
        decode = (
            self._carry, first_tok, patch, self._tables[1], self._knobs[1]
        )
        with self._phase("decode_launch" if piece is None else "prefill_launch"):
            if piece is None:
                *self.pool.arrays, self._carry, nxt, logp = self.runner.decode_step(
                    *self.pool.arrays, *decode
                )
            else:
                *self.pool.arrays, self._carry, nxt, logp, tok, tok_logp = (
                    self._joint(*self.pool.arrays, *decode, *piece[3])
                )
            self._state_n["decodes"] += 1
            self._state_n["decode_rows"] += len(rows)
            self._state_n["decode_tokens"] += sum(r.seq_len + a for _, r, a in rows)
            if self._window:
                self._decode_window_tokens += sum(
                    min(r.seq_len + a, self._window) for _, r, a in rows)
            flight = _Flight([(i, r) for i, r, _ in rows], nxt, logp, self._step_n)
        if piece is not None:
            self._pipe["joint_steps"] += 1
            flight.first = self._chunk_launched(*piece[:3], tok, tok_logp)
        return flight

    def _build_decode(self, rows: list) -> Optional[tuple]:
        """Grow ``rows`` and bring the device's slot state up to them: the
        host sends only what its mirror of that state says has changed.
        Returns (the rows that decode, the first-token operand, the patch
        operand), or None when no row is left."""
        # memory first: every runner needs space for the token it is
        # about to write; the youngest gets evicted when the pool is dry
        self._grow_all([(r, ahead) for _, r, ahead in rows])
        rows = [row for row in rows if row[1].state == RUNNING]
        if not rows:
            return None
        S = self.cfg.max_slots
        feed = np.zeros((2, S), np.int32)  # positions, counters
        tables = np.zeros_like(self._tables[0])
        live = np.zeros(S, np.int32)
        temp = np.zeros(S, np.float32)
        top_k = np.zeros(S, np.int32)
        top_p = np.ones(S, np.float32)
        seeds = np.zeros(S, np.int64)
        patch = np.zeros((S, 4), np.int32)
        ids: list = [None] * S
        known, first_tok = self._slot_ids, self._no_first
        for i, req, ahead in rows:
            pos = req.seq_len + ahead - 1  # the fed token's position
            ctr = len(req.out) + ahead
            feed[:, i] = pos, ctr
            tables[i] = self.pool.table_row(req.id)
            p = req.params
            live[i] = 1
            temp[i] = p.temperature
            top_k[i] = p.top_k
            top_p[i] = p.top_p
            seeds[i] = p.seed & 0xFFFFFFFF
            ids[i] = req.id
            if (
                known is None or known[i] != req.id
                or self._slot_next[0, i] != pos or self._slot_next[1, i] != ctr
            ):
                if self._first is not None and self._first[0] is req:
                    patch[i] = PATCH_JOIN, 0, pos, ctr
                    first_tok = self._first[1]
                else:
                    assert ahead == 0, "a row set from the host has its token there"
                    tok = req.out[-1] if req.out else req.prompt[-1]
                    patch[i] = PATCH_SET, tok, pos, ctr
        for i in range(S):
            if ids[i] is None and (known is None or known[i] is not None):
                patch[i, 0] = PATCH_SET  # emptied: back to (0, 0, 0)
        place = self.runner.place
        sent = 0
        if patch.any():
            patch, sent = place(patch), sent + 1
        else:
            patch = self._no_patch
        if not np.array_equal(tables, self._tables[0]):
            self._tables, sent = (tables, place(tables)), sent + 1
        knobs = pack_knobs(live, temp, top_k, top_p, seeds)
        if not np.array_equal(knobs, self._knobs[0]):
            self._knobs, sent = (knobs, place(knobs)), sent + 1
        self._pipe["uploads"][("none", "partial", "partial", "full")[sent]] += 1
        self._slot_ids = ids
        self._slot_next = feed + live
        self._note_sampler(temp)
        return rows, first_tok, patch

    def _decode_all(self, chunk: bool = False) -> bool:
        """One batched decode step over every RUNNING slot (and, with
        ``chunk``, the step's prefill chunk in the same program): launch the
        next one, THEN read the one in flight, so the device works under
        the emit and everything up to the next launch.  A drafter needs
        the tokens on the host: depth 0, each launch read at once."""
        nxt = self._launch_decode(chunk)
        if nxt is None:
            return self._drain("empty")
        self._pipe["ahead_steps" if self._flight is not None else "serial_steps"] += 1
        if self._drafter is not None:
            self._flight, nxt = nxt, None
        if self._flight is not None:
            with self._phase("decode_fetch"):
                got = self._take_flight()
            with self._phase("emit"):
                self._emit_flight(*got)
                self._flush_streams()
        if self._first is not None:
            self._read_first()
        if nxt is not None:
            # a final chunk that rode this launch: its token is read where
            # this flight is, a step on, never by waiting on the program
            # just launched
            self._flight, self._first = nxt, nxt.first
        return True

    def _spec_decode_all(self, spec_info: dict) -> bool:
        """One speculative step over every RUNNING slot: draft k tokens
        per slot, verify k+1 positions in one jitted call, emit the
        accepted prefix + correction/bonus, roll the ledger back."""
        import jax

        sched = self.scheduler
        kd = self.cfg.spec_k
        active = [
            (i, r)
            for i, r in enumerate(sched.slots)
            if r is not None and r.state == RUNNING
        ]
        if not active:
            return False
        with self._phase("draft"):
            t0 = time.perf_counter()
            draft = self._drafter.propose([r.prompt + r.out for _, r in active])
            draft_s = time.perf_counter() - t0
            self._spec_draft_s += draft_s
            _metrics()["spec_draft_s"].inc(draft_s)
        # drafter confidence gate: when NO slot's proposal is backed by a
        # real match (NGramDrafter.last_matched), the whole window would
        # be a doomed probe — plain-decode this step instead of paying a
        # w-wide verify to learn it.  Hostile workloads thus cost the
        # (host-side, near-free) drafting only; model drafters have no
        # such signal and rely on the acceptance backoff alone.
        matched = getattr(self._drafter, "last_matched", None)
        if matched is not None and not bool(matched.any()):
            return self._decode_all()
        with self._phase("decode_build"):
            draft_by_id = {r.id: draft[row] for row, (_, r) in enumerate(active)}
            # memory next: the window provisionally writes positions
            # seq_len-1 .. seq_len-1+k; the youngest gets evicted when dry
            self._grow_all([(r, kd) for _, r in active])
            active = [(i, r) for i, r in active if r.state == RUNNING]
            if not active:
                return False
            S, W = self.cfg.max_slots, kd + 1
            tokens = np.zeros((S, W), np.int32)
            base_pos = np.zeros(S, np.int32)
            tables = np.zeros((S, self.pool.cfg.max_blocks_per_seq), np.int32)
            temp = np.zeros(S, np.float32)
            top_k = np.zeros(S, np.int32)
            top_p = np.ones(S, np.float32)
            seeds = np.zeros(S, np.uint32)
            counters = np.zeros(S, np.int32)
            for i, req in active:
                tokens[i, 0] = req.out[-1] if req.out else req.prompt[-1]
                tokens[i, 1:] = draft_by_id[req.id]
                base_pos[i] = req.seq_len - 1  # the fed token's position
                tables[i] = self.pool.table_row(req.id)
                p = req.params
                temp[i] = p.temperature
                top_k[i] = p.top_k
                top_p[i] = p.top_p
                seeds[i] = p.seed & 0xFFFFFFFF
                counters[i] = len(req.out)
            self._note_sampler(temp, W)
            self._slot_ids = None  # the window moves rows past the carry
        with self._phase("decode_launch", "verify_launch"):
            *self.pool.arrays, n_acc, out, out_lp = self.runner.verify_step(
                *self.pool.arrays, tokens, base_pos, tables,
                temp, top_k, top_p, seeds, counters,
            )
        with self._phase("decode_fetch", "verify_fetch"):
            n_acc, out, out_lp = jax.device_get((n_acc, out, out_lp))  # ONE host sync
        with self._phase("emit"):
            now = time.time()
            for i, req in active:
                if req.phase_led is not None:
                    _phases.charge(req.phase_led, _phases.SPEC_VERIFY, now)
            emitted = 0
            accepted = 0
            for i, req in active:
                n = int(n_acc[i])
                accepted += n
                _events.record(
                    "llm.verify", request_id=req.trace_id, engine_req=req.id,
                    step=self._step_n, proposed=kd, accepted=n,
                )
                for j in range(n + 1):
                    self._emit(req, int(out[i, j]), float(out_lp[i, j]))
                    emitted += 1
                    if req.finished:
                        # stop token / length cap hit inside the window: the
                        # rest of the acceptance is after-the-end, discard it
                        break
                if not req.finished:
                    # ledger rollback: return the rejected tail's provisional
                    # blocks (device k/v needs none — see cache.shrink_to)
                    self.pool.shrink_to(req.id, req.seq_len)
            proposed = kd * len(active)
            self._spec_proposed += proposed
            self._spec_accepted += accepted
            step_rate = accepted / max(proposed, 1)
            if step_rate < self.cfg.spec_min_accept:
                # low acceptance: back off to plain decode, doubling the
                # pause while probes keep failing (EngineConfig docstring)
                self._spec_backoff = min(
                    max(self._spec_backoff * 2, 2), self.cfg.spec_backoff_max
                )
                self._spec_skip = self._spec_backoff
            else:
                self._spec_backoff = 0
            m = _metrics()
            m["spec_proposed"].inc(proposed)
            m["spec_accepted"].inc(accepted)
            m["spec_accept_rate"].set(step_rate)
            m["tokens_per_step"].set(emitted)
            spec_info.update(
                k=kd,
                slots=len(active),
                proposed=proposed,
                accepted=accepted,
                emitted=emitted,
                draft_s=round(draft_s, 6),
                backoff=self._spec_backoff,
            )
        return True

    def _emit(self, req: Request, tok: int, logp: float = float("nan")) -> None:
        """Record one sampled token: stream it, capture its behavior
        logprob, update latency metrics, finish on stop token /
        max_tokens / model-length cap."""
        t = time.perf_counter()  # the `emit` station's stamp, and `wake`'s start
        m = _metrics()
        if req.first_token_t is None:
            req.first_token_t = now = time.time()
            m["ttft"].observe(now - req.arrival_t)
            _events.record(
                "llm.first_token", request_id=req.trace_id,
                engine_req=req.id, ttft_s=round(now - req.arrival_t, 6),
            )
        elif req.last_token_t is not None:
            m["itl"].observe(t - req.last_token_t)
        req.last_token_t = t
        req.out.append(tok)
        req.out_logprobs.append(logp)
        if req.sink is None:
            req.stream.put(("token", tok, t))
        else:
            req.sink.push(tok, t)
            self._flush = req.sink.flush_soon
        self._tokens_generated += 1
        m["tokens"].inc()
        p = req.params
        if tok in p.stop_token_ids:
            self.scheduler.finish(req, FINISH_STOP)
        elif len(req.out) >= p.max_tokens or req.seq_len >= self.max_model_len:
            self.scheduler.finish(req, FINISH_LENGTH)

    def hbm_ledger(self) -> dict:
        """Live HBM byte accounting (the gauges' source of truth, also
        handy for tests/stats): params, pool total, and the seq-owned /
        cache-only / free partition of usable blocks × block bytes.

        Under ``tp > 1`` a ``per_device`` section attributes the same
        families per device: pool/params from the arrays actually
        resident (head shards + replicated copies — params per device
        EXCEEDS ``params_bytes / tp`` because replicated leaves are a
        full copy each), the block partition scaled by each device's
        local block bytes, the drafter (single-chip) on device 0.  The
        top-level numbers stay pool-wide — the ledger is host-global,
        block ids are not per-shard."""
        bb = self.pool.block_bytes
        counts = self.pool.ledger_counts()
        led = {
            "params_bytes": self._params_bytes,
            "pool_bytes": self.pool.device_bytes,
            "block_bytes": bb,
            "seq_bytes": counts["seq_owned"] * bb,
            "cache_bytes": counts["cache_only"] * bb,
            "free_bytes": counts["free"] * bb,
            "drafter_bytes": self._drafter_bytes,
            # utilization/free_blocks derived from the SAME snapshot —
            # one pool-lock acquisition serves the SLO gauge, stats()
            # and the ledger, and the numbers cannot disagree within one
            # response (separate property reads could interleave with an
            # allocation between the lock acquisitions)
            "free_blocks": counts["free"],
            "utilization": counts["seq_owned"]
            / max(self.pool.cfg.num_blocks - 1, 1),
        }
        if "slots_owned" in counts:
            # a hybrid pool: the partition above is the K/V blocks'; the
            # slots of state are allocated beside them, once
            led["state_bytes"] = self._states.device_bytes
            led["state_seq_bytes"] = counts["slots_owned"] * self._states.block_bytes
        if "window_blocks_held" in counts:
            # blocks of two layer kinds: the partition above is the full
            # layers'; the window layers' sub-pool lies beside it
            led["window_bytes"] = self.pool.windowed.device_bytes
            led["window_seq_bytes"] = counts["window_blocks_held"] * self.pool.window_block_bytes
        if self.cfg.tp > 1:
            pool_dev = self.pool.per_device_bytes()
            par_dev = self.runner.per_device_param_bytes()
            nb = self.pool.cfg.num_blocks
            first = next(iter(pool_dev), None)
            led["per_device"] = {
                dev: {
                    "params_bytes": par_dev.get(dev, 0),
                    "pool_bytes": pool_b,
                    "seq_bytes": counts["seq_owned"] * (pool_b // nb),
                    "cache_bytes": counts["cache_only"] * (pool_b // nb),
                    "free_bytes": counts["free"] * (pool_b // nb),
                    "drafter_bytes": self._drafter_bytes if dev == first else 0,
                }
                for dev, pool_b in pool_dev.items()
            }
        return led

    def _publish_gauges(self) -> None:
        m = _metrics()
        m["running"].set(self.scheduler.num_running)
        m["waiting"].set(self.scheduler.num_waiting)
        led = self.hbm_ledger()
        m["kv_util"].set(led["utilization"])
        m["hbm_params"].set(led["params_bytes"])
        m["hbm_pool"].set(led["pool_bytes"])
        m["hbm_seq"].set(led["seq_bytes"])
        m["hbm_cache"].set(led["cache_bytes"])
        m["hbm_free"].set(led["free_bytes"])
        m["hbm_drafter"].set(led["drafter_bytes"])
        # tp>1: the same gauge NAMES split by a device tag (RL012 keeps
        # the name registry honest — tags are free); the untagged series
        # above stays pool-wide for every existing consumer
        for dev, row in led.get("per_device", {}).items():
            tags = {"device": dev}
            m["hbm_params"].set(row["params_bytes"], tags=tags)
            m["hbm_pool"].set(row["pool_bytes"], tags=tags)
            m["hbm_seq"].set(row["seq_bytes"], tags=tags)
            m["hbm_cache"].set(row["cache_bytes"], tags=tags)
            m["hbm_free"].set(row["free_bytes"], tags=tags)
            m["hbm_drafter"].set(row["drafter_bytes"], tags=tags)
        done = self.scheduler.finish_count
        if done > self._finished_published:
            m["finished"].inc(done - self._finished_published)
            self._finished_published = done
        # service-rate EWMA for deadline-aware admission: sampled at most
        # twice a second so one burst step doesn't whipsaw the estimate.
        # Only GENERATING windows update the average — an idle window is
        # not evidence of slowness, it is no evidence at all, so going
        # idle RESETS the rate (decaying it instead leaves a tiny stale
        # rate that would inflate estimates and spuriously shed the first
        # requests of the next burst).
        now = time.monotonic()
        t0, n0 = self._rate_mark
        if now - t0 >= 0.5:
            new_tokens = self._tokens_generated - n0
            if new_tokens > 0:
                inst = new_tokens / (now - t0)
                self._rate = (
                    inst if self._rate <= 0 else 0.7 * self._rate + 0.3 * inst
                )
            elif not self.scheduler.has_work():
                self._rate = 0.0
            # work pending but zero tokens this window (long prefill,
            # compile): keep the last measured rate
            self._rate_mark = (now, self._tokens_generated)
