"""Paged attention over a block-table KV cache: decode, prefill, verify.

Generalizes ``models.gptj._attend_cached`` (one query row against a dense
per-sequence cache) to the paged layout the ``ray_tpu.llm`` engine uses:
the cluster-wide KV cache is a fixed pool of physical blocks

    k_pool, v_pool : (num_blocks, heads, block_size, head_dim)

and each decode slot owns a *block table* mapping its logical block index
to a physical block id.  The functions here know nothing of layers: the
jitted steps (``llm.model_runner._layer_loop``) hand every layer the
WHOLE engine pool as its free ``(layers * blocks_per_layer, heads,
block_size, head_dim)`` view, with block tables offset by ``layer *
blocks_per_layer`` — a slice ``pool[layer]`` as the operand would be
materialized, a pool-sized copy per layer.  Static shapes throughout —
the pool size, block size, and table width are compile-time constants;
only the table CONTENTS and per-slot lengths are data — so the engine
jits one decode step and reuses it for every admission/eviction pattern.

Three entry points:

* ``paged_attention`` — one query per slot (the decode step).
* ``paged_prefill_attention_xla`` — chunked prefill for ONE sequence.
* ``paged_verify_attention`` — ``w = k+1`` consecutive queries per slot
  (speculative-decode verification): query ``i`` of a slot sits at
  ``positions[s, i]`` and attends causally over the slot's paged cache
  INCLUDING the window's own earlier positions (their k/v are scattered
  in before the attention runs).  The causal intra-window mask is just
  ``cache_pos <= positions[s, i]`` — window k/v live at those positions.

``paged_attention`` and ``paged_verify_attention`` each have two
interchangeable paths behind one signature (same contract as
``ops.attention``):

* ``xla``    — gather the table's blocks into a dense (slots, heads,
  table*block, d) view, masked softmax.  The reference path; also what
  multi-chip pjit partitions cleanly.
* ``pallas`` — ONE scalar-prefetch Pallas kernel (decode is the verify
  kernel at window width 1) whose work follows the blocks a row HOLDS:
  grid ``(slots,)``, one step a slot; the pools stay in HBM and the
  block tables and positions are prefetched scalars.  Inside a step the
  kernel walks the row's own ``ceil(length / block_size)`` blocks — a
  trip count read from the positions, never the table's width — in RUNS
  of about 512 KB of each pool (``_run_blocks``: a function of the
  block's shape): a run is one async copy a block, K and V, into one of
  two VMEM buffers, started while the run before it is in the MXU; the
  next live slot's first run is started before a slot's last one is
  consumed, so the copies form one stream across slots.  No copy is
  issued past a row's length; the last run's ragged tail is masked by
  position; a slot of length 0 costs a grid step and no fetch.  All heads
  of a run meet the queries in ONE matmul in the pool's dtype (a query
  keeps its own head's columns), online-softmax state in float32.  No
  (slots, table*block) score matrix and no gathered cache copy ever
  materializes, and the walk's bound is data: one compiled program
  whatever the batch.  Compiled by Mosaic on a TPU backend (parity
  against the XLA path at the served shapes: ``chip_smoke.py``'s kernel
  phase); interpreted everywhere else, which only tests that ask for
  ``impl="pallas"`` reach (``tests/test_paged_kernel_walk.py``,
  ``tests/test_llm_engine.py``, ``tests/test_llm_spec.py``).

``auto`` follows ONE rule, ``auto_impl``: the Pallas kernel on a TPU
backend when the pool tiles (block_size a multiple of 8, head_dim of
128), else XLA.  The rule reads the POOL's last axis, not the model's head:
a family whose heads are narrower than a lane row (64-wide: LFM2) keeps its
K/V unpadded by laying two key-value heads side by side in one 128-lane pool
row and their query heads on the window axis, each in its own half of the
lanes (``ops.gqa_attention.gqa_paged_attention``), and this kernel, which
knows nothing of it, runs as it is at ``head_dim`` 128; a pool given to it at
64 lanes would take the gathering XLA path.

Convention: table entries past a sequence's allocation MUST point at a
valid physical block (the engine pads with block 0, its reserved trash
block — in the whole-pool view, the layer's own block 0); masking by
``lengths``/``positions`` makes their values irrelevant (the Pallas
kernel never reads them).  Slots with ``length == 0`` produce finite
garbage (XLA: big-negative masking; the kernel: zeros; never NaN) —
callers discard inactive slots.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def auto_impl(block_size: int, head_dim: int) -> str:
    """THE ``impl="auto"`` rule, by platform and shape, for decode and
    verify alike: ``"pallas"`` on a TPU backend when the per-head KV
    block ``(block_size, head_dim)`` tiles (sublanes by 8, lanes by
    128), else ``"xla"``.  Off a TPU the kernel could only run
    interpreted — orders of magnitude slower than compiled XLA — so
    ``auto`` never picks it there.  The rule is per head: under
    ``EngineConfig(tp=N)`` the kernel runs inside a shard_map body on
    ``n_heads // tp`` local heads with every tile shape unchanged, and
    the tp psum happens in the caller after the output projection, so
    the kernel needs no collective."""
    if _on_tpu() and block_size % 8 == 0 and head_dim % 128 == 0:
        return "pallas"
    return "xla"


def first_window_block(position, window: int, block_size: int):
    """The first block a query at ``position`` still sees with a window of
    ``window`` tokens (keys ``> position - window``): every block before it
    lies WHOLLY behind the window.  ``llm.cache.LayerTypedPool`` releases by
    the same rule, on the host."""
    return jnp.maximum(position - (window - 1), 0) // block_size


def window_blocks(tokens: int, block_size: int) -> int:
    """The most blocks ``tokens`` consecutive positions touch."""
    return -(-tokens // block_size) + 1


# ---------------------------------------------------------------------------
# XLA reference path
# ---------------------------------------------------------------------------


def paged_attention_xla(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
) -> jax.Array:
    """q: (slots, heads, d); pools: (num_blocks, heads, block, d);
    block_tables: (slots, tmax) int32; lengths: (slots,) int32 — valid
    cache positions per slot (new token's k/v already written).
    Returns (slots, heads, d) in q.dtype, fp32 softmax accumulation."""
    s, h, d = q.shape
    scale = d**-0.5
    k = k_pool[block_tables]  # (slots, tmax, heads, block, d)
    v = v_pool[block_tables]
    k = k.transpose(0, 2, 1, 3, 4).reshape(s, h, -1, d)
    v = v.transpose(0, 2, 1, 3, 4).reshape(s, h, -1, d)
    logits = jnp.einsum(
        "shd,shkd->shk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    mask = jnp.arange(k.shape[2])[None, None, :] < lengths[:, None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("shk,shkd->shd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_prefill_attention_xla(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_table: jax.Array,
    positions: jax.Array,
) -> jax.Array:
    """Chunked-prefill attention for ONE sequence: each chunk query at
    ``positions[i]`` attends causally over the sequence's paged cache
    (chunk k/v already scattered in).  q: (chunk, heads, d);
    block_table: (tmax,) int32; positions: (chunk,) int32.  Returns
    (chunk, heads, d)."""
    c, h, d = q.shape
    scale = d**-0.5
    with jax.named_scope("paged_attention"):
        k = k_pool[block_table]  # (tmax, heads, block, d)
        v = v_pool[block_table]
        k = k.transpose(1, 0, 2, 3).reshape(h, -1, d)
        v = v.transpose(1, 0, 2, 3).reshape(h, -1, d)
        logits = jnp.einsum(
            "chd,hkd->chk", q.astype(jnp.float32), k.astype(jnp.float32)
        ) * scale
        mask = jnp.arange(k.shape[1])[None, None, :] <= positions[:, None, None]
        logits = jnp.where(mask, logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("chk,hkd->chd", probs, v.astype(jnp.float32))
        return out.astype(q.dtype)


def paged_verify_attention_xla(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    window: int | None = None,
) -> jax.Array:
    """Multi-query verification attention (see module doc).  q: (slots, w,
    heads, d); pools: (num_blocks, heads, block, d); block_tables:
    (slots, tmax) int32; positions: (slots, w) int32 — the absolute cache
    position of each query (its own k/v already written).  Query (s, i)
    attends every cache position ``<= positions[s, i]`` — causal across
    the window because the window's positions are consecutive — and, with
    ``window``, ``> positions[s, i] - window``: only the table's
    ``window_blocks`` entries from the first query's first visible block on
    are gathered (``first_window_block``), so an entry behind them is never
    read.  Returns (slots, w, heads, d) in q.dtype, fp32 softmax
    accumulation."""
    s, w, h, d = q.shape
    scale = d**-0.5
    if window is not None:
        bs, tmax = k_pool.shape[2], block_tables.shape[1]
        n = min(tmax, window_blocks(window + w - 1, bs))
        lo = jnp.minimum(first_window_block(positions[:, 0], window, bs), tmax - n)
        block_tables = jax.vmap(
            lambda row, at: jax.lax.dynamic_slice_in_dim(row, at, n))(block_tables, lo)
    k = k_pool[block_tables]  # (slots, tmax, heads, block, d)
    v = v_pool[block_tables]
    k = k.transpose(0, 2, 1, 3, 4).reshape(s, h, -1, d)
    v = v.transpose(0, 2, 1, 3, 4).reshape(s, h, -1, d)
    logits = jnp.einsum(
        "swhd,shkd->swhk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    at = jnp.arange(k.shape[2])[None, None, None, :]
    if window is not None:
        at = at + (lo * bs)[:, None, None, None]
    mask = at <= positions[:, :, None, None]
    if window is not None:
        mask &= at > positions[:, :, None, None] - window
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("swhk,shkd->swhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

# what one fetch run holds of EACH pool; see ``_run_blocks``
_RUN_BYTES = 512 * 1024


def _run_blocks(heads: int, block_size: int, d: int, itemsize: int, tmax: int) -> int:
    """Blocks in one fetch run: as many KV blocks as make about
    ``_RUN_BYTES`` of one pool (4 at 16 heads x 16 x 256 in bf16, 16 at
    the 4 local heads of ``tp=4``), never more than a table holds.  A
    block alone (32-128 KB) is too short a copy to keep HBM busy; a run is
    the unit the kernel double-buffers and feeds the MXU."""
    return max(1, min(tmax, _RUN_BYTES // (heads * block_size * d * itemsize)))


def _paged_verify_kernel(
    # scalar prefetch
    tables_ref,   # (slots * tmax,) int32 — flattened block tables
    pos_ref,      # (slots * w,) int32 — flattened query positions
    # inputs
    q_ref,        # (1, w * heads, d) — the slot's queries, row = (i, head)
    k_hbm,        # (num_blocks, heads, block, d) — the WHOLE pool, in HBM
    v_hbm,
    # output
    o_ref,        # (1, w * heads, d)
    # scratch (carried across grid steps: the fetch stream spans slots)
    kbuf,         # (2, run, heads, block, d) — two runs of K blocks
    vbuf,
    sems,         # DMA semaphores (2, 2): (k|v, buffer)
    stream,       # SMEM (2,) int32: buffer of the next run; slot whose
                  # first run is already in flight (or none)
    *,
    heads: int,
    block_size: int,
    w: int,
    run: int,
    tmax: int,
    scale: float,
    window: int | None,
):
    s = pl.program_id(0)
    slots = pl.num_programs(0)
    rows, d = q_ref.shape[1], q_ref.shape[2]
    cols = run * heads * block_size

    def first_block(slot):
        # with a ``window``: the first block the slot's FIRST query still
        # sees; the walk starts there, and no table entry before it is read
        return jax.lax.div(jnp.maximum(pos_ref[slot * w] - (window - 1), 0), block_size)

    def blocks_held(slot):
        # the window is consecutive, so the LAST query's position bounds
        # the valid cache
        length = pos_ref[slot * w + (w - 1)] + 1
        held = jnp.minimum(jax.lax.div(length + (block_size - 1), block_size), tmax)
        return held if window is None else held - first_block(slot)

    def for_run(slot, r, buf, act):
        """``act`` on the K and V copy of every block run ``r`` of ``slot``
        holds — none past the row's length."""
        n = jnp.minimum(run, blocks_held(slot) - r * run)

        def body(i, carry):
            if window is None:
                blk = tables_ref[slot * tmax + r * run + i]
            else:
                blk = tables_ref[slot * tmax + first_block(slot) + r * run + i]
            act(pltpu.make_async_copy(k_hbm.at[blk], kbuf.at[buf, i], sems.at[0, buf]))
            act(pltpu.make_async_copy(v_hbm.at[blk], vbuf.at[buf, i], sems.at[1, buf]))
            return carry

        jax.lax.fori_loop(0, n, body, 0)

    def start_run(slot, r, buf):
        for_run(slot, r, buf, lambda copy: copy.start())

    @pl.when(s == 0)
    def _open():
        stream[0] = 0
        stream[1] = -1
        # the tail of a short run keeps what the buffer held before; its
        # probabilities are 0, and 0 x (whatever VMEM held) must be 0
        vbuf[...] = jnp.zeros_like(vbuf)

    held = blocks_held(s)
    n_runs = jax.lax.div(held + (run - 1), run)

    @pl.when(held == 0)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(held > 0)
    def _live():
        first = stream[0]

        @pl.when(stream[1] != s)
        def _cold():
            start_run(s, 0, first)

        q = q_ref[0].astype(kbuf.dtype)                        # (rows, d)
        # column c of a run is (block c // (heads * block), head, token):
        # all heads' scores come out of ONE matmul and a query keeps its
        # own head's columns.  ``reach`` is how far past a column's place
        # in its run the query's position lies, -1 off its head
        col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
        col_head = jax.lax.rem(jax.lax.div(col, block_size), heads)
        col_pos = (
            jax.lax.div(col, heads * block_size) * block_size
            + jax.lax.rem(col, block_size)
        )
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        row_pos = jnp.zeros((rows, 1), jnp.int32)
        for i in range(w):
            row_pos = jnp.where(
                jax.lax.div(row, heads) == i, pos_ref[s * w + i], row_pos
            )
        if window is not None:
            # positions count from the slot's first block walked
            row_pos = row_pos - first_block(s) * block_size
        reach = jnp.where(
            col_head == jax.lax.rem(row, heads), row_pos - col_pos, -1
        )                                                       # (rows, cols)

        def body(r, carry):
            m_prev, l_prev, acc = carry
            buf = jax.lax.rem(first + r, 2)

            # the run after this one goes into the other buffer before
            # this one is waited for: the row's next, or the next live
            # row's first, so a short row pays no cold fetch
            @pl.when(r + 1 < n_runs)
            def _next_run():
                start_run(s, r + 1, 1 - buf)

            @pl.when(r + 1 == n_runs)
            def _next_row():
                nxt = jax.lax.while_loop(
                    lambda j: (j < slots)
                    & (blocks_held(jnp.minimum(j, slots - 1)) == 0),
                    lambda j: j + 1,
                    s + 1,
                )
                stream[1] = nxt

                @pl.when(nxt < slots)
                def _():
                    start_run(nxt, 0, 1 - buf)

            for_run(s, r, buf, lambda copy: copy.wait())
            k = kbuf[buf].reshape(cols, d)
            v = vbuf[buf].reshape(cols, d)
            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                           # (rows, cols)
            seen = reach >= r * (run * block_size)
            if window is not None:
                seen &= reach < r * (run * block_size) + window
            scores = jnp.where(seen, scores, NEG_INF)
            m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(seen, jnp.exp(scores - m_new), 0.0)
            l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
            # p keeps float32's worth of bits on the MXU's bf16 path: the
            # pool's dtype holds its leading bits, a second product the rest
            p_hi = p.astype(v.dtype)
            p_lo = (p - p_hi.astype(jnp.float32)).astype(v.dtype)

            def times_v(part):
                return jax.lax.dot_general(
                    part, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )                                               # (rows, d)

            pv = times_v(p_hi) + times_v(p_lo)
            return m_new, l_new, acc * alpha + pv

        _, l, acc = jax.lax.fori_loop(
            0, n_runs, body,
            (
                jnp.full((rows, 1), NEG_INF, jnp.float32),
                jnp.zeros((rows, 1), jnp.float32),
                jnp.zeros((rows, d), jnp.float32),
            ),
        )
        stream[0] = jax.lax.rem(first + n_runs, 2)
        o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _paged_verify_pallas(q, k_pool, v_pool, block_tables, positions, window=None):
    slots, w, heads, d = q.shape
    _, _, block_size, _ = k_pool.shape
    tmax = block_tables.shape[1]
    run = _run_blocks(heads, block_size, d, k_pool.dtype.itemsize, tmax)
    rows = w * heads
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        # ONE step a slot, in order: the fetch stream and its two buffers
        # carry from a slot to the next
        grid=(slots,),
        in_specs=[
            pl.BlockSpec((1, rows, d), lambda s, tbl, pos: (s, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, rows, d), lambda s, tbl, pos: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, run, heads, block_size, d), k_pool.dtype),
            pltpu.VMEM((2, run, heads, block_size, d), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_verify_kernel, heads=heads, block_size=block_size, w=w,
            run=run, tmax=tmax, scale=d**-0.5, window=window,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, rows, d), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=not _on_tpu(),
        # the name the kernel has in a lowered program and a device trace
        name="paged_attention_decode" if w == 1 else "paged_attention_verify",
    )(block_tables.reshape(-1).astype(jnp.int32),
      positions.reshape(-1).astype(jnp.int32),
      q.reshape(slots, rows, d), k_pool, v_pool)
    return out.reshape(slots, w, heads, d)


# ---------------------------------------------------------------------------
# dispatcher
# ---------------------------------------------------------------------------


def _resolve_impl(impl: str, k_pool: jax.Array) -> str:
    if impl not in ("auto", "xla", "pallas"):
        raise ValueError(
            f"unknown paged attention impl {impl!r}; expected 'auto', 'xla' "
            "or 'pallas'"
        )
    if impl == "auto":
        _, _, block_size, d = k_pool.shape
        return auto_impl(block_size, d)
    return impl


def paged_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    impl: str = "auto",
) -> jax.Array:
    """Single-position attention over a paged KV cache (see module doc).

    q: (slots, heads, head_dim); k_pool/v_pool: (num_blocks, heads,
    block_size, head_dim); block_tables: (slots, tmax) int32; lengths:
    (slots,) int32.  ``impl``: auto | xla | pallas.
    """
    with jax.named_scope("paged_attention"):
        if _resolve_impl(impl, k_pool) == "xla":
            return paged_attention_xla(q, k_pool, v_pool, block_tables, lengths)
        # decode is a verify window of width 1 whose query sits at length - 1
        return _paged_verify_pallas(
            q[:, None], k_pool, v_pool, block_tables, (lengths - 1)[:, None]
        )[:, 0]


def paged_verify_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    positions: jax.Array,
    impl: str = "auto",
    window: int | None = None,
) -> jax.Array:
    """Multi-query verification attention over a paged KV cache (see
    module doc): ``w`` consecutive queries per slot for speculative-decode
    verification, causal intra-window masking by absolute position.

    q: (slots, w, heads, head_dim); k_pool/v_pool: (num_blocks, heads,
    block_size, head_dim); block_tables: (slots, tmax) int32; positions:
    (slots, w) int32.  ``impl``: auto | xla | pallas.  ``window``: a query
    at position ``p`` sees the keys ``> p - window`` alone, and both paths
    START at the first block the slot's first query still sees
    (``first_window_block``): work and bytes follow ``min(length, window)``,
    and a table entry behind that block (one its pool has taken back) is
    never read.
    """
    with jax.named_scope("paged_attention"):
        if _resolve_impl(impl, k_pool) == "xla":
            return paged_verify_attention_xla(
                q, k_pool, v_pool, block_tables, positions, window
            )
        return _paged_verify_pallas(q, k_pool, v_pool, block_tables, positions, window)
