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
* **Attention** — per-head math never crosses heads: q/k/v projections
  are column-parallel (each device computes its own heads), the paged
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
``fork_blocks`` keep their single-chip signatures (the decode's slot
state and every small operand replicated, placed by ``place``) — ``LLMEngine``,
speculative decoding, preemption-recompute, failover ``resume_tokens``
and the prefix cache run UNCHANGED on top; ``EngineConfig(tp=N)`` is
the only switch.  Off-TPU this runs on jax host-platform device-count
meshes (``XLA_FLAGS=--xla_force_host_platform_device_count``), which is
how tier-1 exercises tp=2/4 on CPU; on a TPU the shard bodies' ``auto``
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

On the device the shard bodies carry the one-chip bodies' scope names
(``model_runner.SCOPES``) plus ``tp_sum`` with its halves nested
(``tp_sum/gather``, ``tp_sum/add``).  ``_tp_sum`` also notes itself
while a shard body is traced, so the runner's ledger of the reduction
(``tp_sum_stats``: calls and received bytes of each program as it was
traced) follows the code; ``LLMEngine.stats()`` reports it as
``tp_sum`` beside ``tp``.  What it costs on the chip: PERF.md section 5.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.llm.cache import CacheConfig, KVBlockPool
from ray_tpu.llm.model_runner import (
    PagedModelRunner,
    _advance_slots,
    _chunk_write,
    _decode_sample,
    _fork_impl,
    _layer_loop,
    _layernorm,
    _merge_slots,
    _prefill_sample,
    _rows_write,
    _slots_write,
    _verify_rows,
)
from ray_tpu.ops.paged_attention import (
    paged_attention,
    paged_prefill_attention_xla,
    paged_verify_attention,
)
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
    """Megatron split by param path: q/k/v + mlp_in column-parallel
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
    """``PagedModelRunner`` with the jitted steps shard_map'd over the
    tp mesh.  Wrapper methods (``decode_step``/``verify_step``/
    ``fork_blocks``) and the engine-facing contract are inherited; only
    the traced bodies and parameter placement change."""

    #: NOT inherited: the base class's joint program (a chunk and the decode
    #: rows in one launch) is its single-chip body, and this runner has
    #: shard bodies of its own; the engine finds no offer here and launches
    #: two programs.  Separation, until the string path and this one are one
    #: body under a mesh (ROADMAP D1), when the joint program exists once
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
        super().__init__(cfg, params, block_size, attn_impl)
        if tp < 1:
            raise ValueError(f"tp must be >= 1, got {tp}")
        if cfg.n_heads % tp:
            raise ValueError(f"n_heads={cfg.n_heads} not divisible by tp={tp}")
        if cfg.d_ff % tp:
            raise ValueError(f"d_ff={cfg.d_ff} not divisible by tp={tp}")
        self.tp = tp
        #: site -> {calls, bytes} of ``_tp_sum`` in one execution of that
        #: step program, noted when it was traced (``_tp_layers``)
        self._tp_sums: dict = {}
        self._mesh = make_tp_mesh(tp)
        # inherited _qkv_rows reshapes to this many heads — the ones
        # whose kernels' column shards live on this device
        self.n_local_heads = cfg.n_heads // tp
        self.params = self.prepare_params(params)
        pspecs = self._param_spec_tree()
        # re-jit the step functions over the mesh (the base jits were
        # never traced); donation contract is the base class's — the
        # pool shards update in place
        self._decode = jax.jit(
            jax.shard_map(
                self._decode_shard,
                mesh=self._mesh,
                in_specs=(
                    pspecs,
                    P(None, None, "tp", None, None),
                    P(None, None, "tp", None, None),
                    P(), P(), P(), P(), P(),
                ),
                out_specs=(
                    P(None, None, "tp", None, None),
                    P(None, None, "tp", None, None),
                    P(), P(), P(),
                ),
                check_vma=False,
            ),
            donate_argnums=(1, 2, 3),
        )
        self._verify = jax.jit(
            jax.shard_map(
                self._verify_shard,
                mesh=self._mesh,
                in_specs=(
                    pspecs,
                    P(None, None, "tp", None, None),
                    P(None, None, "tp", None, None),
                    P(), P(), P(), P(), P(), P(), P(), P(),
                ),
                out_specs=(
                    P(None, None, "tp", None, None),
                    P(None, None, "tp", None, None),
                    P(), P(), P(),
                ),
                check_vma=False,
            ),
            donate_argnums=(1, 2),
        )
        self._prefill = jax.jit(
            jax.shard_map(
                self._prefill_shard,
                mesh=self._mesh,
                in_specs=(
                    pspecs,
                    P(None, None, "tp", None, None),
                    P(None, None, "tp", None, None),
                    P(), P(), P(), P(), P(),
                ),
                out_specs=(
                    P(None, None, "tp", None, None),
                    P(None, None, "tp", None, None),
                    P(), P(), P(),
                ),
                check_vma=False,
            ),
            donate_argnums=(1, 2),
        )
        # CoW fork copies whole blocks along axis 1 — head-agnostic, so
        # the single-chip impl runs per-shard unchanged
        self._fork = jax.jit(
            jax.shard_map(
                _fork_impl,
                mesh=self._mesh,
                in_specs=(
                    P(None, None, "tp", None, None),
                    P(None, None, "tp", None, None),
                    P(), P(),
                ),
                out_specs=(
                    P(None, None, "tp", None, None),
                    P(None, None, "tp", None, None),
                ),
                check_vma=False,
            ),
            donate_argnums=(0, 1),
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

    def _shuffle_qkv(self, x: jax.Array) -> jax.Array:
        """GPT's fused qkv projection lays its last axis out ``[Q|K|V]``;
        plain column sharding would hand device i a slice of Q spilling
        into K.  Permute host-side to the concat over devices of
        ``[Q_i|K_i|V_i]`` so each device's contiguous shard splits
        locally into its own head group's q/k/v (shape preserved, so
        ``update_weights`` leaf validation is unaffected)."""
        d = x.shape[-1] // 3
        dl = d // self.tp
        q, k, v = jnp.split(x, 3, axis=-1)
        parts = []
        for i in range(self.tp):
            sl = slice(i * dl, (i + 1) * dl)
            parts.extend([q[..., sl], k[..., sl], v[..., sl]])
        return jnp.concatenate(parts, axis=-1)

    def prepare_params(self, params: dict) -> dict:
        """Sharded ``device_put`` of a (new) weight tree — the
        ``update_weights`` hot-swap path and __init__ share it, so a
        swap lands with the exact placement the compiled steps expect
        (no silent retrace; RL024's runtime twin watches this)."""
        # leaves go host -> their shards directly: staging a whole leaf on
        # the default device first would not fit a model tp exists for
        new = params
        if self.arch == "gpt":
            new = dict(new)
            blocks = dict(new["blocks"])
            qkv = dict(blocks["attn_qkv"])
            qkv["kernel"] = self._shuffle_qkv(qkv["kernel"])
            qkv["bias"] = self._shuffle_qkv(qkv["bias"])
            blocks["attn_qkv"] = qkv
            new["blocks"] = blocks
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: jax.device_put(
                leaf, NamedSharding(self._mesh, _spec_for(path))
            ),
            new,
        )

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
        (``_tp_layers``): a change of the reduction shows here, not only
        in a trace."""
        launched = self.prof.stats()
        totals = {
            k: sum(c[k] * launched.get(s, {"calls": 0})["calls"]
                   for s, c in self._tp_sums.items())
            for k in ("calls", "bytes")
        }
        return dict(totals, per_step=dict(self._tp_sums))

    # -- per-device layer math --------------------------------------------

    def _tp_layer(
        self, x, layer, k, v, base, positions, write, attend, noted
    ):
        """One transformer layer on THIS device's head/ff shard, over the
        whole local pools (``_layer_loop``'s view; ``base`` is this
        layer's first block there).  ``attend(q, k, v, base) -> (rows,
        local_d)`` supplies the step shape's paged attention over the
        local head group; the two row-parallel projections produce
        partial sums reduced over "tp" by ``_tp_sum`` (replicated biases
        added once, after)."""
        dt = x.dtype

        def attn_partial(q, k, v):
            att = attend(q, k, v, base)  # paged_attention names its own scope
            with jax.named_scope("attn_out"):
                return att @ layer["attn_out"]["kernel"].astype(dt)

        def mlp_partial(h):
            with jax.named_scope("mlp"):
                mid = jax.nn.gelu(
                    h @ layer["mlp_in"]["kernel"].astype(dt)
                    + layer["mlp_in"]["bias"].astype(dt)
                )
                return mid @ layer["mlp_out"]["kernel"].astype(dt)

        def biased(h, mod, scope):
            # the replicated bias, added once after the reduction, under
            # the scope in which the one-chip body adds it
            with jax.named_scope(scope):
                return h + layer[mod]["bias"].astype(dt)

        ln1, q, k, v = self._qkv_write(x, layer, k, v, base, positions, write)
        att_p = attn_partial(q, k, v)
        if self.arch == "gptj":
            # parallel residual: attention + MLP partials share ONE
            # fused reduction per layer (half the collectives of the
            # sequential-residual arch below)
            out = biased(
                x + _tp_sum(att_p + mlp_partial(ln1), "tp", noted),
                "mlp_out", "mlp",
            )
        else:
            h = biased(
                x + _tp_sum(att_p, "tp", noted), "attn_out", "attn_out"
            )
            ln2 = _layernorm(h, layer["ln2"]["scale"], layer["ln2"]["bias"])
            out = biased(
                h + _tp_sum(mlp_partial(ln2), "tp", noted), "mlp_out", "mlp"
            )
        return out, k, v

    def _tp_layers(self, site, params, x, k_pool, v_pool, **rows):
        """``_layer_loop`` over ``_tp_layer``.  The loop traces its layer
        once for all of them: what that layer's ``_tp_sum`` calls noted,
        times the layers, is program ``site``'s line in ``tp_sum_stats``."""
        noted: list = []
        out = _layer_loop(
            params["blocks"], x, k_pool, v_pool,
            functools.partial(self._tp_layer, noted=noted, **rows),
        )
        n_layers = k_pool.shape[0]
        # written, never read, while tracing: the program's own account
        # of itself, the one thing meant to be fixed at trace time
        self._tp_sums[site] = {  # raylint: disable=RL009
            "calls": n_layers * len(noted), "bytes": n_layers * sum(noted)
        }
        return out

    # -- shard bodies ------------------------------------------------------
    # Same control flow as the PagedModelRunner._*_impl bodies, with the
    # pool/head math local and the reductions explicit.  Reduced
    # activations are replicated, so lm_head + sampling run identically
    # on every device and the P() out_specs read one copy.

    def _decode_shard(
        self, params, k_pool, v_pool, carry, first_tok, patch, tables, knobs,
    ):
        bs = self.block_size
        tokens, positions, counters = _merge_slots(carry, first_tok, patch)
        S = tokens.shape[0]
        x = self._embed(params, tokens, positions)
        phys = jnp.take_along_axis(tables, (positions // bs)[:, None], axis=1)[:, 0]
        off = positions % bs
        lengths = positions + 1

        def attend(q, k, v, base):
            return paged_attention(
                q, k, v, tables + base, lengths, impl=self.attn_impl
            ).astype(x.dtype).reshape(S, -1)

        x, k_pool, v_pool = self._tp_layers(
            "decode", params, x, k_pool, v_pool,
            positions=positions, write=_slots_write(phys, off, bs), attend=attend,
        )
        logits = self._lm_head(params, x)
        live, nxt, logp = _decode_sample(logits, knobs, counters)
        return (
            k_pool, v_pool, _advance_slots(live, nxt, positions, counters), nxt, logp
        )

    def _verify_shard(
        self, params, k_pool, v_pool, tokens, base_pos, tables,
        temp, top_k, top_p, seeds, counters,
    ):
        cfg = self.cfg
        bs = self.block_size
        S, W = tokens.shape
        tmax = tables.shape[1]
        positions = base_pos[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
        pos_flat = positions.reshape(-1)
        x = self._embed(params, tokens.reshape(-1), pos_flat)
        # overflow positions clamp to trash exactly like the base impl
        valid = pos_flat < tmax * bs
        logical = jnp.minimum(pos_flat // bs, tmax - 1)
        tables_rep = jnp.repeat(tables, W, axis=0)
        phys = jnp.where(
            valid,
            jnp.take_along_axis(tables_rep, logical[:, None], axis=1)[:, 0],
            0,
        )
        off = pos_flat % bs
        nh, hd = self.n_local_heads, cfg.head_dim

        def attend(q, k, v, base):
            return paged_verify_attention(
                q.reshape(S, W, nh, hd), k, v, tables + base, positions,
                impl=self.attn_impl,
            ).astype(x.dtype).reshape(S * W, -1)

        x, k_pool, v_pool = self._tp_layers(
            "verify", params, x, k_pool, v_pool,
            positions=pos_flat, write=_rows_write(phys, off), attend=attend,
        )
        logits = self._lm_head(params, x).reshape(S, W, -1)
        n_acc, out, logp = _verify_rows(
            logits, tokens[:, 1:], seeds, counters, temp, top_k, top_p
        )
        return k_pool, v_pool, n_acc, out, logp

    def _prefill_shard(
        self, params, k_pool, v_pool, tokens, start, n_valid, table, sampling,
    ):
        # chunk is tokens.shape[0] — static under jit, but NOT a static
        # kwarg: shard_map takes positional specs only, and the engine
        # always pads to cfg.prefill_chunk so this still traces once
        chunk = tokens.shape[0]
        positions = start + jnp.arange(chunk, dtype=jnp.int32)
        x = self._embed(params, tokens, positions)

        def attend(q, k, v, base):
            return paged_prefill_attention_xla(
                q, k, v, table + base, positions
            ).astype(x.dtype).reshape(chunk, -1)

        x, k_pool, v_pool = self._tp_layers(
            "prefill", params, x, k_pool, v_pool,
            positions=positions, attend=attend,
            write=_chunk_write(table, start, n_valid, chunk, self.block_size),
        )
        last = x[jnp.maximum(n_valid - 1, 0)]
        logits = self._lm_head(params, last[None, :])[0]
        tok, logp = _prefill_sample(logits, sampling)
        return k_pool, v_pool, logits, tok, logp

    def prefill_chunk(self, k_pool, v_pool, tokens, start, n_valid, table, sampling):
        # base passes chunk= as a static kwarg; the shard body derives it
        return self._call(
            "prefill", self._prefill, len(tokens),
            self.params, k_pool, v_pool, tokens,
            np.int32(start), np.int32(n_valid), table, sampling,
        )
