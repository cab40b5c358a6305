"""Paged-cache model execution: jitted prefill-chunk and decode-step fns.

Bridges the model zoo (``models.gpt``, ``models.gptj``) to the paged KV
cache: where ``gptj_decode`` owns a dense per-call cache, these functions
thread the SHARED block pool through every call — scatter the new
positions' k/v into physical blocks, attend via ``ops.paged_attention``,
and hand back the updated pool arrays (functional updates; the engine
holds the current version).

Four entry shapes, each jitted once per engine:

* ``decode_step`` — (slots,) one token per running slot, batched across
  heterogeneous sequences (different lengths, block tables, sampling
  params).  Inactive slots carry position 0 and an all-trash block table;
  their writes land in reserved block 0 and their sampled tokens are
  discarded host-side.  Every sampled token returns with its behavior
  logprob (``models.sampling`` logprob convention — the RLHF capture
  path), as does every verified window position below.  What a slot
  feeds LIVES ON THE DEVICE ("Slot state" below): the engine launches
  step N+1 before it has read step N's tokens.
* ``prefill_chunk`` — (chunk,) tokens of ONE sequence at positions
  ``start..start+chunk`` (tail-padded; a padded position writes nothing:
  the chunk's k/v enter the pool as the whole blocks they touch,
  ``_chunk_write``).  Returns the last valid position's logits and the token
  sampled from them by the request's own knobs (one row of
  ``_sample_rows``): the final chunk's seeds generation, on the device.
* ``prefill_with_slots`` — the two above as ONE program, for a step that
  carries a chunk AND has rows to decode: the ``slots`` rows and the
  ``chunk`` rows go through the layer loop together, so a layer's weights
  cross HBM once a step where two programs fetched them twice.  The
  tensor-parallel runner and the hooks-body runners do not offer it.
* ``verify_step`` — (slots, k+1) speculative-decode verification: each
  slot feeds its last emitted token plus ``k`` drafted tokens, their k/v
  scatter PROVISIONALLY into the pool, one multi-query paged attention
  (``ops.paged_verify_attention``) yields all ``k+1`` positions' logits,
  and ``models.sampling.speculative_verify`` accepts a prefix + one
  correction/bonus token per slot.  Rejected positions need no device
  rollback — they sit beyond the sequence length, everything masks by
  length, and the next window overwrites them first (the block LEDGER
  rolls back host-side via ``cache.shrink_to``).  Window positions past
  the table's reach scatter to the trash block, so slots at the model-
  length cap stay safe (their surplus logits are discarded host-side).

The bodies are written for a SHARD of the heads and of ``d_ff`` (``-1``,
``n_local_heads``, a layer's two row-parallel products as partial sums that
go through ``_sum``), of which one chip holds the only one:
``llm.multichip`` runs these same bodies under its mesh.

Slot state: the decode program carries ``(token, position, counter)`` of
every slot from one step to the next in ONE donated ``(3, slots)`` int32
array, which it returns advanced by the token it sampled, so the next step
can be launched before this one's tokens are read.  The host says only what
CHANGED, in ``patch`` (``PATCH_*``: a row set from host values, a row whose
first token is the array a final prefill chunk left on the device, a slot
emptied); block tables and the packed per-slot knobs (``pack_knobs``) are
device arrays the engine replaces when its NumPy mirror of them changes.
Small operands reach the device through ``place`` (replicated under ``tp``).

Names on the device: the shared layer math below runs under
``jax.named_scope``s (``SCOPES``), the same names in decode, verify,
prefill and fork, so a profiler trace or a lowered program says which
device op is which after any recompile renumbers ``fusion.N``.  Scopes
are metadata (``op_name``): they change no compiled code.

Static shapes everywhere: slot count, chunk size, window width ``k+1``,
table width, and pool geometry are compile-time constants — admission,
preemption, completion, and per-step acceptance-length changes never
retrace.
"""

from __future__ import annotations

import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu._private import events as _events
from ray_tpu._private import compile_cache
from ray_tpu.models.gpt import GPTConfig, _layernorm
from ray_tpu.util.device_prof import JitProfiler, mosaic_kernels
from ray_tpu.models.gptj import GPTJConfig
from ray_tpu.models.sampling import sample_rows_logprobs, verify_rows_logprobs
from ray_tpu.ops.paged_attention import (
    paged_attention,
    paged_prefill_attention_xla,
    paged_verify_attention,
)


#: the ``jax.named_scope``s of the jitted steps (``llm.multichip`` adds
#: ``tp_sum``): a device op's ``op_name`` holds one of them as a path segment
SCOPES = (
    "embed", "qkv", "kv_write", "paged_attention", "attn_out", "mlp",
    "lm_head", "sample", "kv_fork", "slot_state",
)

#: ``patch[:, 0]`` of the decode program, per slot: feed what the carry
#: holds; feed ``patch[:, 1:]`` = (token, position, counter); or feed
#: that position and counter with the token a final prefill chunk sampled
PATCH_KEEP, PATCH_SET, PATCH_JOIN = 0, 1, 2


def _bits(x, dtype) -> np.ndarray:
    """Host values as the int32 words of a packed operand."""
    return np.asarray(x, dtype).view(np.int32)


def pack_knobs(live, temp, top_k, top_p, seeds) -> np.ndarray:
    """(slots, 5) int32: one upload for what a slot samples with.  ``live``
    is 1 where the carry advances; an empty slot stays at position 0 (one
    trash block for the kernel to walk).  From scalars, (5,): the prefill
    program's one row, its first word the row's counter."""
    return np.stack([
        np.asarray(live, np.int32), _bits(temp, np.float32),
        np.asarray(top_k, np.int32), _bits(top_p, np.float32),
        # mask, don't cast raw: a negative seed overflows a uint32 cell on
        # NumPy >= 2 and the OverflowError would kill the engine loop thread
        _bits(np.asarray(seeds, np.int64) & 0xFFFFFFFF, np.uint32),
    ], axis=-1)


def host_batch(tokens, positions, tables, temp, top_k, top_p, seeds, counters):
    """``decode_step``'s operands after the pools for a batch held wholly
    on the host (a probe, a test: no carry to go on from): every slot is
    set from these arrays."""
    n = len(tokens)
    patch = np.stack(
        [np.full(n, PATCH_SET), tokens, positions, counters], axis=-1
    ).astype(np.int32)
    return (
        np.zeros((3, n), np.int32), np.zeros(1, np.int32), patch,
        np.asarray(tables, np.int32), pack_knobs(np.ones(n), temp, top_k, top_p, seeds),
    )


def _rotary_rows(x: jax.Array, positions: jax.Array, rotary_dim: int) -> jax.Array:
    """GPT-J interleaved rotary with PER-ROW positions. x: (n, heads, hd);
    positions: (n,) int32.  (models.gptj applies one shared position vector
    across the batch; decode slots each sit at a different position.)"""
    inv_freq = 1.0 / (
        10000.0 ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim)
    )
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]  # (n, r/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    rot, pas = x[..., :rotary_dim], x[..., rotary_dim:]
    r = rot.astype(jnp.float32).reshape(*rot.shape[:-1], rotary_dim // 2, 2)
    x1, x2 = r[..., 0], r[..., 1]
    c = cos[:, None, :]
    s = sin[:, None, :]
    o1 = x1 * c - x2 * s
    o2 = x2 * c + x1 * s
    out = jnp.stack([o1, o2], axis=-1).reshape(rot.shape).astype(x.dtype)
    return jnp.concatenate([out, pas], axis=-1) if pas.shape[-1] else out


def _scatter_kv(pool: jax.Array, vals: jax.Array, phys: jax.Array, off: jax.Array):
    """Write per-row k or v into physical blocks of the WHOLE pool, seen as
    ``_layer_loop`` carries it.  pool: (layers * num_blocks, heads, block,
    d); vals: (n, heads, d); phys: (n,) int32 block ids IN THAT VIEW (the
    layer's first block + the table's entry); off: (n,) int32.

    The form for rows of which several may share a block (verify: a window
    of a few positions a slot).  One scatter index a (row, head), on
    purpose: the shorter ``pool.at[phys, :, off, :].set(vals)`` (a scatter
    whose update window spans heads AND d) makes XLA transpose the pool:
    compiled for a v5e at the one-chip cell's size it holds a temporary of
    2,114,025,984 B, one whole pool, against 0 B for this index (AOT, jax
    0.9.0 / libtpu 0.0.34; tests/test_llm_pool_inplace.py pins the same on
    the CPU backend).  The price is the index: 77-93 ns each on a v5e,
    whatever the pool's size, because a v5e lays the pool out in ``(8,
    128)(2, 1)`` tiles over (block, d): the 16 rows of one (block, head)
    are ONE tile, and one row is a masked read-modify-write of half-words
    (PERF.md section 5)."""
    heads = vals.shape[1]
    with jax.named_scope("kv_write"):
        return pool.at[
            phys[:, None], jnp.arange(heads)[None, :], off[:, None], :
        ].set(vals)


def _scatter_kv_blocks(pool: jax.Array, rows: jax.Array, ids: jax.Array, mask):
    """``_scatter_kv`` by WHOLE blocks, for a step that knows which blocks
    its rows fall into and that no two of its rows' blocks are one (the
    trash block aside): the same pool afterwards, bit for bit, with one
    scatter index a BLOCK, each a run of whole tiles.  pool: (layers *
    num_blocks, heads, block, d); ids: (n,) block ids IN THAT VIEW; rows:
    what each block's rows would become, broadcastable to (n, heads, block,
    d); mask: (n, 1, block, 1) bool, true where a block row IS written.

    The blocks' present content is gathered, ``rows`` laid over it where
    ``mask`` says, the blocks scattered back, in place: a block keeps every
    row that is not written.  Several ``ids`` may be 0: each writes back
    what it read there, so whole blocks leave the trash block as it was.
    (Block by block with ``dynamic_update_slice`` is 0.5 ms a chunk faster
    in today's prefill program and NOT kept: around the same write alone
    XLA re-laid the whole pool out for it, a 2.1 GB temporary, and a
    decode's 64 blocks a layer cost three times the rows' form under
    ``tp=4``; PERF.md section 6, PR 39.)"""
    with jax.named_scope("kv_write"):
        return pool.at[ids].set(jnp.where(mask, rows, pool[ids]))


def _rows_write(phys, off):
    """``write(pool, vals, base)`` row by row (``_scatter_kv`` at block
    ``base + phys``): the verify step, whose window rows share blocks."""
    return lambda pool, vals, base: _scatter_kv(pool, vals, base + phys, off)


def _slots_write(phys, off, block: int):
    """``write(pool, vals, base)`` of a decode: ONE position of each of
    many sequences, so each row is alone in its block (empty slots meet in
    the trash block) and goes as that whole block: 32 indices a layer and a
    pool on one chip where the rows' form has 512."""
    mask = (jnp.arange(block, dtype=jnp.int32) == off[:, None])[:, None, :, None]
    return lambda pool, vals, base: _scatter_kv_blocks(
        pool, vals[:, :, None, :], base + phys, mask
    )


def _chunk_blocks(table: jax.Array, start, n_valid, chunk: int, block: int):
    """Where a prefill chunk's k/v go, as whole blocks.  A chunk is
    ``chunk`` consecutive positions of ONE sequence from ``start``, the
    first ``n_valid`` of them real, so it touches the ``chunk // block + 1``
    blocks from ``start // block`` on.  Returns ``(ids, shift, mask)``: ids
    (nb,) int32, the table's entry of each touched block, 0 (trash) for one
    that holds no valid position (past the valid rows, or past the table's
    reach); shift, a scalar: block row ``(j, r)`` holds chunk row ``j *
    block + r - shift``; mask (nb, 1, block, 1) bool, true where that chunk
    row is a valid one."""
    nb = chunk // block + 1
    first, shift = start // block, start % block
    logical = first + jnp.arange(nb, dtype=jnp.int32)
    rows = jnp.arange(nb * block, dtype=jnp.int32).reshape(nb, block) - shift
    mask = (rows >= 0) & (rows < n_valid)
    ids = jnp.where(
        mask.any(axis=1), table[jnp.minimum(logical, table.shape[0] - 1)], 0
    )
    return ids, shift, mask[:, None, :, None]


def _chunk_write(table, start, n_valid, chunk: int, block: int):
    """``write(pool, vals, base)`` of a prefill chunk: the 9 whole blocks
    that 128 consecutive positions touch, where the rows' form has 2,048
    indices a layer and a pool.  A start inside a block (a prefix hit that
    diverged there, after its copy-on-write fork) keeps that block's
    earlier tokens; a padded row writes nothing; no block before ``start //
    block`` is touched (it may be shared).  All but ``base +`` and the
    rows' own reordering is computed here, once a step, outside the layer
    loop."""
    ids, shift, mask = _chunk_blocks(table, start, n_valid, chunk, block)
    nb = ids.shape[0]

    def write(pool, vals, base):
        with jax.named_scope("kv_write"):
            # chunk row j * block + r - shift sits at j * block + r of the slice
            padded = jnp.pad(vals, ((block, block), (0, 0), (0, 0)))
            rows = jax.lax.dynamic_slice_in_dim(padded, block - shift, nb * block)
            rows = rows.reshape(nb, block, *vals.shape[1:]).transpose(0, 2, 1, 3)
        return _scatter_kv_blocks(pool, rows, base + ids, mask)

    return write


def _carry_loop(blocks, x, pools: tuple, layer_fn):
    """Scan over the stacked layer weights with ``pools`` as CARRY, each
    whole, so that a layer writes into the buffer the caller donated and
    reads that same buffer.  Each pool is ``(L, N, ...)`` and rides as the
    free ``(L * N, ...)`` view, in which layer ``l``'s entry ``n`` is ``l *
    N + n``: ``layer_fn(x, layer, *views, base) -> (x, *views)`` gets the
    views and ``base = l * N``.  The loop is as long as ``blocks`` is
    deep: a pool may hold more layers than this loop runs (the layers of
    another segment), and with no pool at all ``base = l``.  Returns (x,
    *pools), pools in their own shape."""
    n_layers = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    n = pools[0].shape[1] if pools else 1

    def body(carry, inputs):
        layer, base = inputs
        return layer_fn(carry[0], layer, *carry[1:], base), None

    (x, *views), _ = jax.lax.scan(
        body,
        (x, *(p.reshape((-1,) + p.shape[2:]) for p in pools)),
        (blocks, jnp.arange(n_layers, dtype=jnp.int32) * n),
    )
    return (x, *(v.reshape(p.shape) for v, p in zip(views, pools)))


def _layer_loop(blocks, x, k_pool, v_pool, layer_fn):
    """THE layer loop of every jitted step over K/V pools (decode, verify,
    prefill, on one chip and under ``tp``): ``_carry_loop`` with
    the two pools.  A pool that rides a scan as ``xs``/``ys`` is sliced per
    layer and stacked again: six pool-sized copies a step on a v5e
    (PERF.md, PR 24).

    k_pool/v_pool: (L, NB, H, BS, D).  Inside the loop they are the free
    (L * NB, H, BS, D) view, in which layer ``l``'s block ``b`` is block
    ``l * NB + b``: ``layer_fn(x, layer, k, v, base) -> (x, k, v)`` gets
    that view and ``base = l * NB``, and adds ``base`` to every block id
    it writes or reads (the block tables handed to ``ops.paged_attention``).
    How a layer's k/v enter the pool follows from the step's shape, fixed
    when it is traced: a prefill chunk (consecutive positions of ONE
    sequence: ``_chunk_write``) and a decode (one position of many:
    ``_slots_write``) write the whole blocks their rows fall into
    (``_scatter_kv_blocks``); a verify window, whose rows share blocks,
    goes row by row (``_rows_write`` → ``_scatter_kv``).  All leave the
    same pool, and none may use a scatter window over heads
    (``_scatter_kv``: a pool-sized temporary).  Returns (x, k_pool,
    v_pool), pools in their own shape."""
    return _carry_loop(blocks, x, (k_pool, v_pool), layer_fn)


def _sample_rows(logits, seeds, counters, temp, top_k, top_p):
    """Per-row sampling with per-request determinism: row i's key derives
    from (seeds[i], counters[i]) only, so a request draws the same tokens
    no matter which slot or step it lands in.  Returns (tokens (n,),
    logprobs (n,)) — the chosen-token behavior logprob rides along free
    (``models.sampling`` module doc).  One batched call under the ``sample``
    scope: a batch with no sampled row skips the sort, and one with a few
    sorts those alone (``models.sampling._draw_rows``)."""
    with jax.named_scope("sample"):
        return sample_rows_logprobs(logits, seeds, counters, temp, top_k, top_p)


def _abstract(x) -> jax.ShapeDtypeStruct:
    """An operand's shape, dtype and — where it was placed on purpose (a
    sharded pool, a weight) — placement: enough to lower a step again,
    holding no buffer."""
    placed = isinstance(x, jax.Array) and x.committed
    return jax.ShapeDtypeStruct(
        jnp.shape(x), jnp.result_type(x), sharding=x.sharding if placed else None
    )


def _fork_impl(*rest):
    """Copy-on-write block fork for the prefix cache, ``rest`` any number
    of paged arrays ``(L, NB, ...)`` (K and V, a latent pool), then ``src,
    dst``: duplicate whole physical blocks across every layer — ``pool[:,
    dst[i]] = pool[:, src[i]]``.  A block copy is a memmove; recomputing
    the same positions through the model is L layer matmuls — the fork
    wins by orders of magnitude.  Unused lanes pad with (0, 0): trash
    copied onto trash, harmless and value-deterministic even with
    duplicate dst indices.  Head-agnostic, so it runs per shard unchanged."""
    *pools, src, dst = rest
    with jax.named_scope("kv_fork"):
        return tuple(p.at[:, dst].set(p[:, src]) for p in pools)


def _verify_rows(logits, draft, seeds, counters, temp, top_k, top_p):
    """Per-slot speculative verification (same per-request determinism as
    ``_sample_rows``: window token i keys off (seed, counter + i)).
    logits: (S, W, V); draft: (S, W-1).  Returns (n_accepted (S,),
    out_tokens (S, W), out_logprobs (S, W))."""
    with jax.named_scope("sample"):
        return verify_rows_logprobs(
            logits, draft, seeds, counters, temp, top_k, top_p
        )


def _f32(words):
    return jax.lax.bitcast_convert_type(words, jnp.float32)


def _u32(words):
    return jax.lax.bitcast_convert_type(words, jnp.uint32)


def _merge_slots(carry, first_tok, patch):
    """What each slot feeds this step: the carry, or the host's patch."""
    with jax.named_scope("slot_state"):
        mode = patch[:, 0]
        keep = mode == PATCH_KEEP
        tokens = jnp.where(
            keep, carry[0], jnp.where(mode == PATCH_JOIN, first_tok[0], patch[:, 1])
        )
        return (
            tokens,
            jnp.where(keep, carry[1], patch[:, 2]),
            jnp.where(keep, carry[2], patch[:, 3]),
        )


def _advance_slots(live, nxt, positions, counters):
    """The carry the next step feeds from: the sampled token, one position
    and one counter on; an empty slot stays (0, 0, 0)."""
    with jax.named_scope("slot_state"):
        return jnp.stack([jnp.where(live > 0, nxt, 0), positions + live, counters + live])


def _decode_sample(logits, knobs, counters):
    """The decode batch's tokens by its packed knobs. Returns (live, tokens,
    logprobs)."""
    live = knobs[:, 0]
    nxt, logp = _sample_rows(
        logits, _u32(knobs[:, 4]), counters, _f32(knobs[:, 1]), knobs[:, 2],
        _f32(knobs[:, 3]),
    )
    return live, nxt, logp


def _prefill_sample(logits, sampling):
    """One row of ``_sample_rows`` on a chunk's last logits (V,), by the
    request's own ``pack_knobs(counter, ...)``. Returns ((1,) token, (1,)
    logprob)."""
    w = sampling[:, None]
    return _sample_rows(
        logits[None, :], _u32(w[4]), w[0], _f32(w[1]), w[2], _f32(w[3])
    )


@jax.jit
def _concat_last(*parts):
    return jnp.concatenate(parts, axis=-1)


class StepRunner:
    """What every runner of jitted steps shares, whatever its sequences
    hold on the device: the compile marker, the first-call record that
    ``kernels_in_steps`` lowers again, the retrace detector, placement."""

    arch = "?"

    def __init__(self, cfg: Any, params: dict):
        compile_cache.ensure_compile_cache()
        self.cfg = cfg
        self.params = params
        #: the tree this runner was GIVEN, as shapes and dtypes (no arrays):
        #: what a later tree is held to (``LLMEngine.update_weights``),
        #: whatever form ``prepare_params`` keeps it in on the device
        self.given = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)), params
        )
        self._compiled: set = set()  # (fn, shape-key)s already traced
        #: site -> (jitted fn, abstract operands, static kwargs) of its
        #: first call: what kernels_in_steps lowers again, so the report
        #: describes the step the engine runs and not a copy of its signature
        self._first_operands: dict = {}
        #: site -> wall seconds of its first call (trace + compile, or the
        #: persistent-cache load) — set-up time, reported by device_report
        self.first_call_s: dict = {}
        #: site -> that call split by jax's own timers (the compile
        #: listener's totals on both sides of it): ``trace_s``, ``lower_s``,
        #: ``compile_s`` (the compiler, or the cache's load: ``cache_hit``)
        #: and ``run_s``, the rest: dispatch and whatever the call waited for
        self.first_call: dict = {}
        self._totals_before = None  # set by a site's first call alone
        # device-step profiler: per-call wall time into device_step_seconds
        # {site=decode|prefill|verify|fork} + retrace detection against the
        # jit cache size — a site recompiling after its warmup baseline
        # emits llm.retrace and trips the retrace-storm SLO (per-runner so
        # two engines in one process never compare cache sizes)
        self.prof = JitProfiler(event="llm.retrace")

    def _note_compile(self, fn: str, key: Any, t0: float) -> None:
        """Flight-recorder marker for each jit trace+compile: the first
        call per (fn, static-shape) pays the compile, and that wall time
        dominating a serve replica's init (or a mid-traffic retrace, which
        should NEVER happen — static shapes) is exactly what a postmortem
        needs to see.  Subsequent steady-state calls record nothing."""
        if (fn, key) in self._compiled:
            return
        self._compiled.add((fn, key))
        whole = time.perf_counter() - t0
        self.first_call_s[fn] = round(whole, 3)
        before, self._totals_before = self._totals_before, None
        split = {}
        if before is None:  # the site under a second shape (a retrace):
            self.first_call.pop(fn, None)  # first_call_s is that call's now
        else:
            trace_s, lower_s, compile_s, requests, hits = (
                b - a for a, b in zip(before, compile_cache.totals())
            )
            split = self.first_call[fn] = {
                "trace_s": trace_s, "lower_s": lower_s, "compile_s": compile_s,
                "cache_hit": requests > 0 and hits == requests,
                "run_s": whole - trace_s - lower_s - compile_s,
            }
        _events.record(
            "llm.compile", fn=fn, shape=str(key), arch=self.arch,
            first_call_s=self.first_call_s[fn], **split,
        )

    def prepare_params(self, params: dict) -> dict:
        """A tree of ``given``'s structure in the form and placement the
        compiled steps expect.  Here that is host->device conversion; the
        paged runners also keep q / k / v as one leaf, the tensor-parallel
        one shards; a runner's ``__init__`` and every hot-swap
        (``LLMEngine.update_weights``) go through here, so swapped weights
        land exactly like the originals."""
        return jax.tree_util.tree_map(jnp.asarray, params)

    def _call(self, site: str, fn, key: Any, *args, **static):
        """Run one jitted step: remember its first call's operands
        (abstractly), time it, and feed the compile marker and the
        retrace detector."""
        t0 = time.perf_counter()
        if site not in self._first_operands:
            self._first_operands[site] = (
                fn, jax.tree_util.tree_map(_abstract, args), static
            )
            self._totals_before = compile_cache.totals()
        out = fn(*args, **static)
        self._note_compile(site, key, t0)
        self.prof.note(site, fn, time.perf_counter() - t0)
        return out

    def kernels_in_steps(self) -> dict:
        """site -> names of the Mosaic kernels inside that jitted step's
        lowered program, for every step called so far, at the operands it
        was called with: what the attention dispatch rule actually put
        into the step (none = plain XLA ops), read from the program
        instead of trusted from the config.  Lowering only — nothing
        compiles or runs."""
        return {
            site: mosaic_kernels(fn.lower(*args, **static))
            for site, (fn, args, static) in self._first_operands.items()
        }

    def place(self, x):
        """A small host operand onto the device the steps run on, to stay
        there over many steps (replicated under ``tp``): uncommitted, like
        the pool and the weights, so a step's cache key never changes."""
        return jax.device_put(x)


class PagedModelRunner(StepRunner):
    """Owns the jitted step functions for one (config, params) pair."""

    def __init__(self, cfg: Any, params: dict, block_size: int, attn_impl: str = "auto"):
        if isinstance(cfg, GPTJConfig):
            self.arch = "gptj"
        elif isinstance(cfg, GPTConfig):
            if cfg.n_experts > 0:
                raise NotImplementedError(
                    "this runner serves dense GPT only: models.gpt's expert "
                    "layer is the training path's and drops tokens over its "
                    "capacity; a family with its own serving_body() serves "
                    "experts droplessly (models.kimi_k2, ops.moe)")
            self.arch = "gpt"
        else:
            raise TypeError(f"unsupported model config {type(cfg).__name__}")
        super().__init__(cfg, params)
        self.block_size = block_size
        self.attn_impl = attn_impl
        # heads THIS runner's traced bodies see: all of them single-chip;
        # the tensor-parallel subclass (llm.multichip) narrows this to its
        # per-device head group and runs the same bodies
        self.n_local_heads = cfg.n_heads
        # donate the pool buffers: a step writes its rows into the buffers
        # it was given and hands the same buffers back.  Donation alone did
        # not make that so: it takes the pool as the layer loop's CARRY
        # (_layer_loop); as the scan's xs/ys the pool was copied six times a
        # step, more than the step's math.  tests/test_llm_pool_inplace.py
        # holds every step to it through the compiled program's temp size
        self._decode = jax.jit(self._decode_impl, donate_argnums=(1, 2, 3))
        self._prefill = jax.jit(self._prefill_impl, donate_argnums=(1, 2))
        self._prefill_with_slots = jax.jit(
            self._prefill_with_slots_impl, donate_argnums=(1, 2, 3)
        )
        self._verify = jax.jit(self._verify_impl, donate_argnums=(1, 2))
        self._fork = jax.jit(_fork_impl, donate_argnums=(0, 1))
        self.params = self.prepare_params(params)

    # -- the weights as the steps read them ----------------------------------

    def prepare_params(self, params: dict) -> dict:
        """The GIVEN tree as the compiled steps take it: on the device, and
        a GPT-J layer's ``q`` / ``k`` / ``v`` kernels ``(L, d, d)`` as ONE
        leaf ``attn_qkv.kernel`` ``(L, d, 3d)``, ``[Q | K | V]`` along its
        last axis as ``arch="gpt"`` is given it, so that ``_qkv_rows`` is
        one product whose operand is the layer loop's slice of the stacked
        leaf.  (Three products of three sliced leaves each cost a v5e a
        copy of the slice into fast memory and a re-laid copy of that: 2.4
        ms of a 12.3 ms decode, PERF.md section 6, PR 65.)  The three are
        not in the resident tree: it holds the given tree's bytes, once."""
        blocks = dict(params["blocks"])
        if self.arch == "gptj":
            parts = [blocks.pop(m)["kernel"] for m in "qkv"]
            blocks["attn_qkv"] = {"kernel": self._pack(parts)}
        return super().prepare_params(dict(params, blocks=blocks))

    def _pack(self, parts: list) -> jax.Array:
        """``parts`` side by side along their last axis, on the device.
        Waited for: when this returns, parts that nothing else holds are
        gone, so the engine's pool is made beside ONE copy of them."""
        return jax.block_until_ready(_concat_last(*map(jnp.asarray, parts)))

    # -- shared layer math -------------------------------------------------

    def _qkv_rows(self, layer, h, positions):
        """h: (n, d) post-ln hidden → q/k/v (n, heads, hd): ONE product
        against ``attn_qkv`` (this device's ``[Q | K | V]`` columns), its
        bias for gpt, rotary applied for gptj."""
        cfg = self.cfg
        dt = h.dtype
        nh, hd = self.n_local_heads, cfg.head_dim
        with jax.named_scope("qkv"):
            qkv = h @ layer["attn_qkv"]["kernel"].astype(dt)
            if self.arch == "gpt":
                qkv = qkv + layer["attn_qkv"]["bias"].astype(dt)
            q, k, v = (
                x.reshape(h.shape[0], nh, hd) for x in jnp.split(qkv, 3, axis=-1)
            )
            if self.arch == "gptj":
                q = _rotary_rows(q, positions, cfg.rotary_dim)
                k = _rotary_rows(k, positions, cfg.rotary_dim)
        return q, k, v

    def _mlp(self, layer, h):
        """The MLP over this device's ``d_ff`` columns: a PARTIAL sum,
        without ``mlp_out``'s bias (``_layer`` adds it once, after ``_sum``)."""
        dt = h.dtype
        with jax.named_scope("mlp"):
            mid = jax.nn.gelu(
                h @ layer["mlp_in"]["kernel"].astype(dt)
                + layer["mlp_in"]["bias"].astype(dt)
            )
            return mid @ layer["mlp_out"]["kernel"].astype(dt)

    def _attn_out(self, layer, att_flat):
        """The output projection of this device's heads: a partial sum too."""
        with jax.named_scope("attn_out"):
            return att_flat @ layer["attn_out"]["kernel"].astype(att_flat.dtype)

    def _sum(self, x):
        """A row-parallel product's partial sums, summed over the devices
        that hold the shards: on one chip the partial sum IS the sum."""
        return x

    def _qkv_write(self, x, layer, k, v, base, positions, write):
        """The head of a layer:
        ln1, the rows' q/k/v, and their k/v written into the whole pools
        (``_layer_loop``'s view) by the step's ``write(pool, vals, base)``
        (``_chunk_write`` / ``_slots_write`` / ``_rows_write``, which add
        ``base`` to their block ids).  Returns (ln1, q, k, v)."""
        ln1 = _layernorm(x, layer["ln1"]["scale"], layer["ln1"]["bias"])
        q, kr, vr = self._qkv_rows(layer, ln1, positions)
        k = write(k, kr.astype(k.dtype), base)
        v = write(v, vr.astype(v.dtype), base)
        return ln1, q, k, v

    def _layer(self, x, layer, k, v, base, positions, write, attend):
        """One transformer layer on THIS device's head / ``d_ff`` shard (on
        one chip: all of them), over the whole local pools
        (``_layer_loop``'s view; ``base`` is this layer's first block there).
        ``attend(q, k, v, base) -> (rows, local heads * head_dim)`` supplies
        the step shape's paged attention, its block tables offset by
        ``base``.  The two row-parallel products are partial sums that go
        through ``_sum``; each replicated bias is added once, after, under
        the scope of its product."""
        dt = x.dtype

        def biased(h, mod, scope):
            with jax.named_scope(scope):
                return h + layer[mod]["bias"].astype(dt)

        ln1, q, k, v = self._qkv_write(x, layer, k, v, base, positions, write)
        att_p = self._attn_out(layer, attend(q, k, v, base))
        if self.arch == "gptj":
            # parallel residual: attention and MLP partials share ONE sum a
            # layer (under tp: half the collectives of the arch below)
            out = biased(x + self._sum(att_p + self._mlp(layer, ln1)), "mlp_out", "mlp")
        else:
            h = biased(x + self._sum(att_p), "attn_out", "attn_out")
            ln2 = _layernorm(h, layer["ln2"]["scale"], layer["ln2"]["bias"])
            out = biased(h + self._sum(self._mlp(layer, ln2)), "mlp_out", "mlp")
        return out, k, v

    def _layers(self, site, params, x, k_pool, v_pool, **rows):
        """``_layer_loop`` over ``_layer`` with the step's ``rows``
        (positions, write, attend), for step program ``site`` (the
        tensor-parallel runner keeps its reductions' ledger by it)."""
        return _layer_loop(
            params["blocks"], x, k_pool, v_pool, functools.partial(self._layer, **rows)
        )

    def _embed(self, params, tokens, positions):
        # params flows through the TRACED argument, never self.params: the
        # jitted executables cache across weight hot-swaps
        # (LLMEngine.update_weights), so anything read from self here would
        # bake the ORIGINAL weights into the compiled step as constants —
        # a swap would then silently update only the layer stack
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        with jax.named_scope("embed"):
            x = params["embed"]["tokens"][tokens].astype(dt)
            if self.arch == "gpt":
                # clamp: padded prefill-tail positions may run past the table
                pos = jnp.minimum(positions, cfg.seq_len - 1)
                x = x + params["embed"]["pos"][pos].astype(dt)
        return x

    def _lm_head(self, params, h):
        with jax.named_scope("lm_head"):
            h = _layernorm(h, params["ln_f"]["scale"], params["ln_f"]["bias"])
            logits = h.astype(jnp.float32) @ params["lm_head"]["kernel"]
            if self.arch == "gptj":
                logits = logits + params["lm_head"]["bias"]
        return logits

    # -- decode step -------------------------------------------------------

    def _decode_impl(
        self,
        params,
        k_pool,      # (L, NB, H, BS, D)
        v_pool,
        carry,       # (3, S) int32 — (token, position, counter) left by the
                     # step before: donated, returned advanced
        first_tok,   # (1,) int32 — a final prefill chunk's token (PATCH_JOIN)
        patch,       # (S, 4) int32 — (PATCH_*, token, position, counter)
        tables,      # (S, T) int32
        knobs,       # (S, 5) int32 — pack_knobs
    ):
        bs = self.block_size
        # tokens: the token being FED per slot; positions: its position (==
        # cache length before it); counters: index of the token being sampled
        tokens, positions, counters = _merge_slots(carry, first_tok, patch)
        x = self._embed(params, tokens, positions)  # (S, d)
        phys = jnp.take_along_axis(tables, (positions // bs)[:, None], axis=1)[:, 0]
        off = positions % bs
        lengths = positions + 1

        def attend(q, k, v, base):
            return paged_attention(
                q, k, v, tables + base, lengths, impl=self.attn_impl
            ).astype(x.dtype).reshape(x.shape[0], -1)

        x, k_pool, v_pool = self._layers(
            "decode", params, x, k_pool, v_pool,
            positions=positions, write=_slots_write(phys, off, bs), attend=attend,
        )
        logits = self._lm_head(params, x)  # (S, V)
        live, nxt, logp = _decode_sample(logits, knobs, counters)
        return (
            k_pool, v_pool, _advance_slots(live, nxt, positions, counters), nxt, logp
        )

    def decode_step(self, k_pool, v_pool, carry, first_tok, patch, tables, knobs):
        return self._call(
            "decode", self._decode, jnp.shape(tables)[0],
            self.params, k_pool, v_pool, carry, first_tok, patch, tables, knobs,
        )

    # -- speculative verification step -------------------------------------

    def _verify_impl(
        self,
        params,
        k_pool,      # (L, NB, H, BS, D)
        v_pool,
        tokens,      # (S, W) int32 — last emitted token + k drafts per slot
        base_pos,    # (S,) int32 — position of tokens[:, 0]
        tables,      # (S, T) int32
        temp,        # (S,) f32
        top_k,       # (S,) i32
        top_p,       # (S,) f32
        seeds,       # (S,) u32
        counters,    # (S,) i32 — output index of the window's first token
    ):
        cfg = self.cfg
        bs = self.block_size
        S, W = tokens.shape
        tmax = tables.shape[1]
        positions = base_pos[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
        pos_flat = positions.reshape(-1)                     # (S*W,)
        x = self._embed(params, tokens.reshape(-1), pos_flat)  # (S*W, d)
        # window positions can provisionally run past the table's reach
        # (a slot one emit away from the model-length cap still feeds k
        # drafts): clamp the gather and scatter the overflow to trash —
        # the engine never emits tokens from those positions
        valid = pos_flat < tmax * bs
        logical = jnp.minimum(pos_flat // bs, tmax - 1)
        tables_rep = jnp.repeat(tables, W, axis=0)           # (S*W, T)
        phys = jnp.where(
            valid,
            jnp.take_along_axis(tables_rep, logical[:, None], axis=1)[:, 0],
            0,
        )
        off = pos_flat % bs

        def attend(q, k, v, base):
            return paged_verify_attention(
                q.reshape(S, W, self.n_local_heads, cfg.head_dim),
                k, v, tables + base, positions, impl=self.attn_impl,
            ).astype(x.dtype).reshape(S * W, -1)

        x, k_pool, v_pool = self._layers(
            "verify", params, x, k_pool, v_pool,
            positions=pos_flat, write=_rows_write(phys, off), attend=attend,
        )
        logits = self._lm_head(params, x).reshape(S, W, -1)  # (S, W, V)
        n_acc, out, logp = _verify_rows(
            logits, tokens[:, 1:], seeds, counters, temp, top_k, top_p
        )
        return k_pool, v_pool, n_acc, out, logp

    def verify_step(self, k_pool, v_pool, tokens, base_pos, tables,
                    temp, top_k, top_p, seeds, counters):
        return self._call(
            "verify", self._verify, tuple(jnp.shape(tokens)),
            self.params, k_pool, v_pool, tokens, base_pos, tables,
            temp, top_k, top_p, seeds, counters,
        )

    # -- copy-on-write block fork (llm.prefix_cache) -----------------------

    def fork_blocks(self, k_pool, v_pool, src, dst):
        """Duplicate physical blocks ``src[i] → dst[i]`` across all
        layers (``(F,)`` int32 each, pad unused lanes with 0→0).  The
        engine calls this right after a cache-aware admission whose
        prompt diverges INSIDE a cached block: the copy makes the shared
        prefix positions of the fork valid, and prefill resumes at the
        divergence point."""
        return self._call("fork", self._fork, len(src), k_pool, v_pool, src, dst)

    # -- prefill chunk -----------------------------------------------------

    def _prefill_impl(
        self,
        params,
        k_pool,
        v_pool,
        tokens,     # (chunk,) int32, tail-padded
        start,      # scalar int32 — position of tokens[0]
        n_valid,    # scalar int32 — valid tokens in this chunk
        table,      # (T,) int32 — THIS sequence's block table
        sampling,   # (5,) int32 — pack_knobs(counter, ...) of this request
    ):
        # static under jit: the engine pads every chunk to cfg.prefill_chunk,
        # so this traces once
        chunk = tokens.shape[0]
        positions = start + jnp.arange(chunk, dtype=jnp.int32)
        x = self._embed(params, tokens, positions)  # (chunk, d)

        def attend(q, k, v, base):
            return paged_prefill_attention_xla(
                q, k, v, table + base, positions
            ).astype(x.dtype).reshape(chunk, -1)

        x, k_pool, v_pool = self._layers(
            "prefill", params, x, k_pool, v_pool,
            positions=positions, attend=attend,
            write=_chunk_write(table, start, n_valid, chunk, self.block_size),
        )
        last = x[jnp.maximum(n_valid - 1, 0)]  # (d,)
        logits = self._lm_head(params, last[None, :])[0]  # (V,)
        tok, logp = _prefill_sample(logits, sampling)
        return k_pool, v_pool, logits, tok, logp

    def prefill_chunk(self, k_pool, v_pool, tokens, start, n_valid, table, sampling):
        # start / n_valid go in as host scalars: a jnp.int32() here is an
        # eager program of its own in front of every chunk
        return self._call(
            "prefill", self._prefill, len(tokens),
            self.params, k_pool, v_pool, tokens,
            np.int32(start), np.int32(n_valid), table, sampling,
        )

    # -- a prefill chunk AND the decode rows, one program --------------------

    def _prefill_with_slots_impl(
        self,
        params,
        k_pool,
        v_pool,
        carry,       # ``_decode_impl``'s operands: the slots' rows
        first_tok,
        patch,
        tables,
        knobs,
        tokens,      # ``_prefill_impl``'s operands: ONE sequence's chunk
        start,
        n_valid,
        table,
        sampling,
    ):
        """A step that carries a chunk, as ONE program: the ``S`` slots'
        rows and the chunk's ``chunk`` rows go through the layer loop
        together, so a layer's weights cross HBM once for both where a
        prefill program followed by a decode program fetched them twice.
        Row-wise work (ln1, q / k / v, rotary, ``attn_out``, the MLP,
        ``lm_head``) is one product over ``S + chunk`` rows; what differs by
        the rows' shape is done side by side: the two K/V writes
        (``_slots_write``, ``_chunk_write``: the chunk's sequence and the
        decoding rows own different blocks, so neither reads what the other
        writes here), the two attentions (the decode kernel over the slots,
        ``paged_prefill_attention_xla`` over the chunk) and the two samplers.
        A FINAL chunk's token leaves with this program's decode tokens: its
        row joins the NEXT launch (``PATCH_JOIN``).  Returns what the two
        programs return: pools, the advanced carry, the slots' tokens and
        logprobs, the chunk's token and logprob.

        The NAME is read: a trace's readers tell programs apart by it, and
        this is "the program of a step that carries a chunk" (``prefill``),
        not a plain decode."""
        bs = self.block_size
        S, chunk = tables.shape[0], tokens.shape[0]
        s_tokens, s_pos, counters = _merge_slots(carry, first_tok, patch)
        c_pos = start + jnp.arange(chunk, dtype=jnp.int32)
        positions = jnp.concatenate([s_pos, c_pos])
        x = self._embed(params, jnp.concatenate([s_tokens, tokens]), positions)
        phys = jnp.take_along_axis(tables, (s_pos // bs)[:, None], axis=1)[:, 0]
        lengths = s_pos + 1
        slots_write = _slots_write(phys, s_pos % bs, bs)
        chunk_write = _chunk_write(table, start, n_valid, chunk, bs)

        def write(pool, vals, base):
            return chunk_write(slots_write(pool, vals[:S], base), vals[S:], base)

        def attend(q, k, v, base):
            slots = paged_attention(
                q[:S], k, v, tables + base, lengths, impl=self.attn_impl
            ).astype(x.dtype).reshape(S, -1)
            rows = paged_prefill_attention_xla(
                q[S:], k, v, table + base, c_pos
            ).astype(x.dtype).reshape(chunk, -1)
            return jnp.concatenate([slots, rows])

        x, k_pool, v_pool = self._layers(
            "prefill_with_slots", params, x, k_pool, v_pool,
            positions=positions, write=write, attend=attend,
        )
        last = x[S + jnp.maximum(n_valid - 1, 0)]
        logits = self._lm_head(params, jnp.concatenate([x[:S], last[None, :]]))
        live, nxt, logp = _decode_sample(logits[:S], knobs, counters)
        tok, tok_logp = _prefill_sample(logits[S], sampling)
        return (
            k_pool, v_pool, _advance_slots(live, nxt, s_pos, counters), nxt, logp,
            tok, tok_logp,
        )

    def prefill_with_slots(self, k_pool, v_pool, carry, first_tok, patch, tables,
                           knobs, tokens, start, n_valid, table, sampling):
        """``decode_step`` and ``prefill_chunk`` in one launch.  A runner
        OFFERS this by having it: the engine launches a step's chunk with
        its decode where it finds the method, and as two programs where it
        finds None (``llm.multichip``; the hooks-body runners have none)."""
        return self._call(
            "prefill_with_slots", self._prefill_with_slots,
            (jnp.shape(tables)[0], len(tokens)),
            self.params, k_pool, v_pool, carry, first_tok, patch, tables, knobs,
            tokens, np.int32(start), np.int32(n_valid), table, sampling,
        )
