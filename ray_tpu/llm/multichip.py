"""Tensor-parallel paged inference — the multi-chip LLM engine substrate.

The single-chip serving stack (``llm.cache`` pool, ``llm.model_runner``
jitted steps, ``ops.paged_attention``) caps the servable model at one
chip's HBM.  This module lifts exactly that stack onto a 1-axis
``("tp",)`` mesh (``parallel.mesh.make_tp_mesh``) with the classic
Megatron column/row split, chosen so the PAGED layout shards for free:

* **KV block pool** — head axis sharded, ``P(None, None, "tp", None,
  None)`` over ``(layers, num_blocks, heads, block_size, head_dim)``.
  Block ids are GLOBAL (every device holds the same blocks' local
  heads), so the host-side ledger, block tables, prefix-cache radix
  tree, watchdog ``audit()`` and CoW fork bookkeeping are untouched —
  the only sharded thing is the payload.
* **Attention** — per-head math never crosses heads: the q/k/v projection
  is column-parallel (ONE leaf ``attn_qkv`` whose shard on device i is
  ``[Q_i | K_i | V_i]``, ``_pack``: each device computes its own heads), the paged
  gather/scatter and softmax run on the local head group, and only the
  output projection is row-parallel (one reduction per layer).
* **MLP** — ``mlp_in`` column-parallel, ``mlp_out`` row-parallel,
  second reduction.  GPT-J's parallel residual lets attention and MLP
  share a single fused reduction per layer.
* **Everything else** (embedding, layernorms, lm_head, sampling) is
  replicated: reduced activations are identical on all devices, so
  every device samples the same token and the engine reads one
  replicated result.

The three jitted entry points (decode / prefill / verify) and the CoW
``fork_blocks`` are the single-chip BODIES (``model_runner``'s ``_*_impl``,
written for a shard of the heads and of ``d_ff``) under ``shard_map``, with
their signatures (the decode's slot state and every small operand
replicated, placed by ``place``) — ``LLMEngine``,
speculative decoding, preemption-recompute, failover ``resume_tokens``
and the prefix cache run UNCHANGED on top; ``EngineConfig(tp=N)`` is
the only switch.  Off-TPU this runs on jax host-platform device-count
meshes (``XLA_FLAGS=--xla_force_host_platform_device_count``), which is
how tier-1 exercises tp=2/4 on CPU; on a TPU the bodies' ``auto``
attention is the Mosaic-compiled paged kernel over each device's local
heads (``ops.paged_attention.auto_impl``).  Weights and pool are born
sharded (``param_shardings`` as the seeded init's ``out_shardings``,
``jnp.zeros(..., device=sharding)`` for the pool): no device ever holds
a whole leaf of either.

Numerics: splitting the two row-parallel contractions across devices
changes the floating-point reduction order, so activations drift from
the single-chip engine by ~1 ulp per layer.  Greedy argmax and
fixed-seed sampling are robust to that in float32 (pinned by
``tests/test_llm_multichip.py``'s tp=1 vs tp=2/4 identity matrix); the
per-head attention path itself is bitwise identical per head.  WITHIN a
tp engine a row's result must not depend on where the row sits in a
step's batch — the prefix cache recomputes a prompt's tail at other
rows of the prefill chunk and promises the same tokens — so the
partials are summed in a fixed device order (``_tp_sum``), not by
``psum``: a TPU all-reduce adds the devices' contributions in an order
that depends on the element's place in the buffer, which in bf16 moved
tokens between a cold prompt and its prefix hit on four v5e chips
(PERF.md, PR 21).

On the device the programs carry the one-chip scope names
(``model_runner.SCOPES``) plus ``tp_sum`` with its halves nested
(``tp_sum/gather``, ``tp_sum/add``).  ``_tp_sum`` also notes itself
while a body is traced, so the runner's ledger of the reduction
(``tp_sum_stats``: calls and received bytes of each program as it was
traced) follows the code; ``LLMEngine.stats()`` reports it as
``tp_sum`` beside ``tp``.  What it costs on the chip: PERF.md section 5.
"""

from __future__ import annotations

import threading
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.llm.cache import CacheConfig, KVBlockPool
from ray_tpu.llm.model_runner import PagedModelRunner, _fork_impl
from ray_tpu.parallel.mesh import make_tp_mesh


def _tp_sum(x: jax.Array, axis: str, noted: list) -> jax.Array:
    """Sum of every ``axis`` device's ``x``, identical on all of them,
    each element added in device order in float32 whatever its row:
    gather (exact data movement), then add.  Both halves sit under the
    ``tp_sum`` scope, each under a scope of its own, so a trace splits
    the data movement from the summation.  Each call appends to
    ``noted`` the bytes a device receives in it (``tp_sum_stats``)."""
    with jax.named_scope("tp_sum"):
        with jax.named_scope("gather"):
            parts = jax.lax.all_gather(x, axis)
        noted.append((parts.size - x.size) * x.dtype.itemsize)
        with jax.named_scope("add"):
            parts = parts.astype(jnp.float32)
            total = parts[0]
            for i in range(1, parts.shape[0]):
                total = total + parts[i]
            return total.astype(x.dtype)


def _spec_for(path) -> P:
    """Megatron split by param path: q/k/v (as a tree is GIVEN them, and
    the runner's one ``attn_qkv``) + mlp_in column-parallel
    (output dim sharded, biases ride along), attn_out + mlp_out
    row-parallel (input dim sharded, replicated biases added after
    the reduction), everything else replicated."""
    names = [getattr(p, "key", None) for p in path]
    if names and names[0] == "blocks" and len(names) >= 3:
        mod, slot = names[1], names[-1]
        if mod in ("q", "k", "v", "attn_qkv", "mlp_in"):
            return P(None, None, "tp") if slot == "kernel" else P(None, "tp")
        if mod in ("attn_out", "mlp_out") and slot == "kernel":
            return P(None, "tp", None)
    return P()


def param_shardings(params, tp: int):
    """The placement the tp runner's compiled steps expect, as a tree of
    ``NamedSharding`` matching ``params`` (arrays or abstract shapes) —
    ``serve.llm`` hands it to its jitted seeded init as ``out_shardings``
    so each device generates only its own shard."""
    mesh = make_tp_mesh(tp)
    return jax.tree_util.tree_map_with_path(
        lambda path, _leaf: NamedSharding(mesh, _spec_for(path)), params
    )


def _per_device_bytes(mesh, leaves) -> dict:
    """device-id label -> local bytes actually resident on that device
    (replicated leaves count once PER device — that copy is real HBM).
    The HBM ledger's per-device attribution reads this."""
    out = {str(d.id): 0 for d in mesh.devices.flat}
    for leaf in leaves:
        for sh in getattr(leaf, "addressable_shards", ()):
            key = str(sh.device.id)
            if key in out:
                out[key] += int(sh.data.nbytes)
    return out


class ShardedKVBlockPool(KVBlockPool):
    """KV block pool whose device arrays are head-sharded over the tp
    mesh.  The host ledger (free list, refcounts, audit) is inherited
    verbatim — block ids are global, so every ledger invariant and the
    watchdog's leak audit hold independent of the mesh size."""

    def __init__(
        self,
        cfg: CacheConfig,
        n_layers: int,
        n_heads: int,
        head_dim: int,
        dtype="float32",
        *,
        tp: int = 1,
    ):
        if tp < 1:
            raise ValueError(f"tp must be >= 1, got {tp}")
        if n_heads % tp:
            raise ValueError(
                f"n_heads={n_heads} not divisible by tp={tp} — the pool "
                "shards the head axis"
            )
        self.tp = tp
        self._mesh = make_tp_mesh(tp)
        # spelled without trailing Nones, the way jax spells the jitted
        # steps' OUTPUT shardings: the pool a step hands back must compare
        # equal to the one it was given, or every jit site grows a second
        # cache entry on its next call and the retrace detector
        # (util.device_prof reads the jit cache size) reports a recompile
        # that never happened
        super().__init__(
            cfg, n_layers, n_heads, head_dim, dtype,
            sharding=NamedSharding(self._mesh, P(None, None, "tp")),
        )

    def per_device_bytes(self) -> dict:
        """Local pool bytes per device — ``device_bytes / tp`` each, the
        whole point of sharding the pool."""
        return _per_device_bytes(self._mesh, (self.k, self.v))


class TensorParallelPagedModelRunner(PagedModelRunner):
    """``PagedModelRunner`` with its step bodies shard_map'd over the tp
    mesh.  The traced bodies, the layer, the wrapper methods
    (``decode_step``/``verify_step``/``prefill_chunk``/``fork_blocks``) and
    the engine-facing contract are inherited; what changes is parameter
    placement, the mesh around each program and ``_sum``, a collective."""

    #: NOT inherited: no sharded joint program (a chunk and the decode rows
    #: in one launch) is built here, so the engine finds no offer and
    #: launches two programs.  The inherited body would run under
    #: ``_on_mesh`` as the others do: ROADMAP Queue 1, a claim of its own
    prefill_with_slots = None

    def __init__(
        self,
        cfg: Any,
        params: dict,
        block_size: int,
        attn_impl: str = "auto",
        *,
        tp: int,
    ):
        if tp < 1:
            raise ValueError(f"tp must be >= 1, got {tp}")
        if cfg.n_heads % tp:
            raise ValueError(f"n_heads={cfg.n_heads} not divisible by tp={tp}")
        if cfg.d_ff % tp:
            raise ValueError(f"d_ff={cfg.d_ff} not divisible by tp={tp}")
        # before the base class, whose last act is ``prepare_params``
        self.tp = tp
        self._mesh = make_tp_mesh(tp)
        self._concat_columns = jax.jit(self._concat_columns_impl)
        super().__init__(cfg, params, block_size, attn_impl)
        #: site -> {calls, bytes} of ``_tp_sum`` in one execution of that
        #: step program, noted when it was traced (``_layers``)
        self._tp_sums: dict = {}
        #: ``.noted``: what the ``_sum`` calls of the layer being traced on
        #: this thread have noted (the engine's thread traces a first call
        #: while a ``device_report()`` lowers the others again)
        self._tracing = threading.local()
        # the inherited bodies reshape to this many heads: the ones whose
        # kernels' column shards live on this device
        self.n_local_heads = cfg.n_heads // tp
        weights = (self._param_spec_tree(),)
        # re-jit the inherited bodies over the mesh (the base jits were
        # never traced); donation contract is the base class's — the
        # pool shards update in place
        self._decode = self._on_mesh(self._decode_impl, weights, 5, 3, (1, 2, 3))
        self._verify = self._on_mesh(self._verify_impl, weights, 8, 3, (1, 2))
        self._prefill = self._on_mesh(self._prefill_impl, weights, 5, 3, (1, 2))
        self._fork = self._on_mesh(_fork_impl, (), 2, 0, (0, 1))

    def _on_mesh(self, body, weights: tuple, n_in: int, n_out: int, donate: tuple):
        """``body`` as one program over the mesh: its operands are
        ``weights`` (the specs of a leading weight tree, or none), the two
        pools with their heads sharded, then ``n_in`` replicated operands;
        it returns the pools and ``n_out`` replicated results (reduced
        activations are the same on every device, so ``lm_head`` and the
        sampler run identically on each and ``P()`` reads one copy).
        ``donate``: the operands updated in place."""
        pool = P(None, None, "tp", None, None)
        return jax.jit(
            jax.shard_map(
                body,
                mesh=self._mesh,
                in_specs=(*weights, pool, pool, *((P(),) * n_in)),
                out_specs=(pool, pool, *((P(),) * n_out)),
                check_vma=False,
            ),
            donate_argnums=donate,
        )

    # -- parameter placement ----------------------------------------------

    def place(self, x):
        """Replicated over the mesh, spelled as jax spells the steps' own
        replicated outputs (the carry a decode hands back)."""
        return jax.device_put(x, NamedSharding(self._mesh, P()))

    def _param_spec_tree(self):
        return jax.tree_util.tree_map_with_path(
            lambda path, _leaf: _spec_for(path), self.params
        )

    def _pack(self, parts: list) -> jax.Array:
        """``parts`` (a layer's Q, K and V columns, each whole) as ONE
        column-parallel leaf whose last axis is the concatenation over
        devices of ``[Q_i | K_i | V_i]``: plain column sharding of ``[Q | K
        | V]`` would hand device i a slice of Q spilling into K, where
        this shard splits locally into its own head group's q / k / v
        (``_qkv_rows``).  Each part goes to its column shards (host ->
        shards directly; a part that ``param_shardings`` placed stays
        where it is) and a device concatenates its own three: nothing is
        gathered, no device ever holds a whole part."""
        sharding = NamedSharding(self._mesh, self._columns(jnp.ndim(parts[0])))
        return jax.block_until_ready(
            self._concat_columns(*(jax.device_put(x, sharding) for x in parts))
        )

    @staticmethod
    def _columns(rank: int) -> P:
        return P(*(None,) * (rank - 1), "tp")

    def _concat_columns_impl(self, *parts):
        """Each device's own column shards side by side."""
        spec = self._columns(parts[0].ndim)
        return jax.shard_map(
            lambda *xs: jnp.concatenate(xs, axis=-1), mesh=self._mesh,
            in_specs=(spec,) * len(parts), out_specs=spec,
        )(*parts)

    def prepare_params(self, params: dict) -> dict:
        """The given tree on the mesh, each leaf by ``_spec_for``, q / k /
        v as ONE leaf ``attn_qkv`` by ``_pack``: GPT-J's three kernels,
        GPT's fused ``[Q | K | V]`` kernel and bias by their thirds."""
        blocks = dict(params["blocks"])
        if self.arch == "gptj":
            fused = {"kernel": [blocks.pop(m)["kernel"] for m in "qkv"]}
        else:  # thirds of a host leaf are views: nothing is staged whole
            fused = {
                slot: (jnp.split if isinstance(x, jax.Array) else np.split)(x, 3, axis=-1)
                for slot, x in blocks.pop("attn_qkv").items()
            }
        # leaves go host -> their shards directly: staging a whole leaf on
        # the default device first would not fit a model tp exists for
        new = jax.tree_util.tree_map_with_path(
            lambda path, leaf: jax.device_put(
                leaf, NamedSharding(self._mesh, _spec_for(path))
            ),
            dict(params, blocks=blocks),
        )
        new["blocks"]["attn_qkv"] = {
            slot: self._pack(parts) for slot, parts in fused.items()
        }
        return new

    def per_device_param_bytes(self) -> dict:
        """device-id label -> param bytes resident there (column/row
        shards + this device's copy of every replicated leaf)."""
        return _per_device_bytes(
            self._mesh, jax.tree_util.tree_leaves(self.params)
        )

    # -- the reduction's ledger -------------------------------------------

    def tp_sum_stats(self) -> dict:
        """Count and received bytes (per device) of the ``_tp_sum`` calls
        in every step launched so far, and ``per_step`` of one execution
        of each program, as its layer noted them when it was traced
        (``_layers``): a change of the reduction shows here, not only
        in a trace."""
        launched = self.prof.stats()
        totals = {
            k: sum(c[k] * launched.get(s, {"calls": 0})["calls"]
                   for s, c in self._tp_sums.items())
            for k in ("calls", "bytes")
        }
        return dict(totals, per_step=dict(self._tp_sums))

    # -- the collective of the inherited layer -----------------------------

    def _sum(self, x):
        return _tp_sum(x, "tp", self._tracing.noted)

    def _layers(self, site, params, x, k_pool, v_pool, **rows):
        """The inherited loop, which traces its layer once for all of them:
        what that layer's ``_sum`` calls noted, times the layers, is
        program ``site``'s line in ``tp_sum_stats``."""
        noted = self._tracing.noted = []
        out = super()._layers(site, params, x, k_pool, v_pool, **rows)
        n_layers = k_pool.shape[0]
        # written, never read, while tracing: the program's own account
        # of itself, the one thing meant to be fixed at trace time
        self._tp_sums[site] = {  # raylint: disable=RL009
            "calls": n_layers * len(noted), "bytes": n_layers * sum(noted)
        }
        return out
