"""Multi-head LATENT attention over a paged pool of latent rows.

What a token leaves in the cache is ONE row a layer, shared by every head:
``[c (rank), k_r (rope), 0 ...]``, the normed key-value latent and the
rotated key part, padded to whole 128-lane tiles (576 -> 640 at the
published sizes).  The pool is ``(blocks, block, width)``: no head axis and
no values, a token's value being the row's first ``rank`` lanes.

Two forms of the same mathematics (``tests/test_llm_kimi_parity.py`` holds
one to the other):

* ``latent_decode_attention`` -- ABSORBED: the up-projection of the keys is
  folded into the query (``q_lat = q_nope W_k[head]``), so a head's score is
  ``[q_lat, q_rope] . row`` and its output ``softmax . row[:rank]``, to be
  expanded by ``W_v[head]`` by the caller.  Every head reads the SAME row:
  ``heads * (width + rank) * 2`` operations a byte pair, at the ridge of a
  v5e where a K/V head's decode is bandwidth alone.  One query a sequence
  (the decode step), over the sequence's block table.
* ``latent_chunk_attention`` -- EXPANDED: a prefill chunk of ONE sequence
  against the rows its table holds (its own already written).  With 512
  queries against 17k rows that is half the absorbed form's operations
  (``(192 + 128)`` a pair and the expansion once, against ``(576 + 512)``).

Each has two interchangeable paths behind one signature, as
``ops.paged_attention``; ``auto`` is the kernel on a TPU backend when the
shapes tile, else ``xla`` (the reference path, and what runs off a TPU):

* decode: ``xla`` gathers the table's rows (a copy of every row a step);
  ``pallas`` is a scalar-prefetch kernel that walks each sequence's own
  blocks in runs, double-buffered, all heads against a run in ONE matmul,
  online softmax in float32; nothing is gathered and no row past a
  sequence's length is read.
* chunk: ``xla`` expands keys and values a group of heads at a time and
  takes a dense masked softmax over the table's whole width: its float32
  scores cross HBM six times (measured: 117 of a chunk's 142 ms on a v5e at
  the published sizes, PR 45).  ``pallas`` expands every head's keys and
  values ONCE (two plain matrix products) and runs a flash kernel over them
  (``latent_attention_chunk``): key tiles stream through VMEM, the scores
  never leave it, and a tile past the chunk's last position is neither
  fetched anew nor computed.  What sets its pace is the MXU (PR 51; the
  kernel it replaced spent 38% of a grid step outside the products):

  - a grid step holds FOUR heads' key tile and walks it in sub-tiles of 384
    keys as one straight line, the next sub-tile's score product written
    BEFORE this one's softmax (Mosaic's scheduler keeps the order it is
    given: that, not the scheduler, is what puts the products under the
    ``exp``), the walk running on across the step's heads;
  - the running maximum and sum are held REPLICATED over 128 lanes, (rows,
    128): as (rows, 1) columns every use of one against a score tile is a
    lane broadcast through the XLU, which then sets the pace of a
    sub-tile (a sub-tile of 256 cost 7.0 ms a layer against the whole
    tile's 3.6);
  - the two score products are ONE 192-deep contraction over ``[nope |
    rope]`` (no float32 add over the tile); the scale stays in float32
    on the scores' side (on ``score - maximum``: the VALU has the room),
    the queries coming rounded from ``_project``, whose signature the
    benchmark's controls override;
  - a tile every query of the chunk sees whole takes a path with no iota,
    compare or select; the one or two tiles the diagonal crosses keep the
    mask.  Both paths are the same operations in the same order, sub-tile
    edges are multiples of 384 from key 0 whatever ``start`` is, and a
    row's statistics are its own: a query's output is the same to the bit
    whichever chunk brings it (a prefix hit's tokens are the cold run's).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.paged_attention import NEG_INF, _on_tpu

#: tokens of one fetch run of the kernel: what is double-buffered and meets
#: the queries in one matmul (4 blocks of 128 x 640 bf16: 640 KB a buffer)
_RUN_TOKENS = 512


def padded_width(rank: int, rope: int) -> int:
    """Lanes of a pool row: ``rank + rope`` up to whole 128-lane tiles."""
    return -(-(rank + rope) // 128) * 128


def auto_impl(block_size: int, width: int, rank: int) -> str:
    if _on_tpu() and block_size % 8 == 0 and width % 128 == 0 and rank % 128 == 0:
        return "pallas"
    return "xla"


def latent_decode_xla(q, pool, tables, positions, *, rank: int, scale: float):
    s, h, w = q.shape
    rows = pool[tables].reshape(s, -1, w)                       # (S, T * block, W)
    scores = jnp.einsum("shw,stw->sht", q, rows,
                        preferred_element_type=jnp.float32) * scale
    seen = jnp.arange(rows.shape[1])[None, None, :] <= positions[:, None, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, NEG_INF), axis=-1)
    return jnp.einsum("sht,str->shr", probs.astype(rows.dtype), rows[..., :rank],
                      preferred_element_type=jnp.float32)


def _latent_decode_kernel(
    tables_ref,   # scalar prefetch: (slots * tmax,) int32
    pos_ref,      # scalar prefetch: (slots,) int32
    q_ref,        # (1, heads, width)
    pool_hbm,     # (blocks, block, width), in HBM
    o_ref,        # (1, heads, rank) float32
    buf,          # VMEM (2, run, block, width)
    sems,         # DMA semaphores (2,)
    *, block_size: int, run: int, tmax: int, rank: int, scale: float,
):
    s = pl.program_id(0)
    heads, width = q_ref.shape[1], q_ref.shape[2]
    cols = run * block_size
    length = pos_ref[s] + 1
    held = jnp.minimum(jax.lax.div(length + (block_size - 1), block_size), tmax)
    n_runs = jax.lax.div(held + (run - 1), run)

    def for_run(r, slot, act):
        """``act`` on the copy of every block run ``r`` holds, none past
        the sequence's length."""
        def body(i, carry):
            blk = tables_ref[s * tmax + r * run + i]
            act(pltpu.make_async_copy(pool_hbm.at[blk], buf.at[slot, i], sems.at[slot]))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(run, held - r * run), body, 0)

    @pl.when(s == 0)
    def _open():
        # a short run's tail keeps what the buffer held: its probabilities
        # are 0, and 0 x (whatever VMEM held) must be 0
        buf[...] = jnp.zeros_like(buf)

    for_run(0, 0, lambda copy: copy.start())
    q = q_ref[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)

    def body(r, carry):
        m_prev, l_prev, acc = carry
        slot = jax.lax.rem(r, 2)

        @pl.when(r + 1 < n_runs)
        def _next():
            for_run(r + 1, 1 - slot, lambda copy: copy.start())

        for_run(r, slot, lambda copy: copy.wait())
        rows = buf[slot].reshape(cols, width)
        scores = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        ) * scale                                               # (heads, cols)
        seen = col + r * cols < length
        scores = jnp.where(seen, scores, NEG_INF)
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(seen, jnp.exp(scores - m_new), 0.0)
        pv = jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                       # (heads, rank)
        return m_new, l_prev * alpha + p.sum(axis=-1, keepdims=True), acc * alpha + pv

    _, l, acc = jax.lax.fori_loop(
        0, n_runs, body,
        (jnp.full((heads, 1), NEG_INF, jnp.float32),
         jnp.zeros((heads, 1), jnp.float32),
         jnp.zeros((heads, rank), jnp.float32)),
    )
    o_ref[0] = acc / jnp.maximum(l, 1e-30)


def _latent_decode_pallas(q, pool, tables, positions, *, rank: int, scale: float):
    slots, heads, width = q.shape
    _, block_size, _ = pool.shape
    tmax = tables.shape[1]
    run = max(1, min(tmax, _RUN_TOKENS // block_size))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots,),
        in_specs=[
            pl.BlockSpec((1, heads, width), lambda s, tbl, pos: (s, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, heads, rank), lambda s, tbl, pos: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, run, block_size, width), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _latent_decode_kernel, block_size=block_size, run=run, tmax=tmax,
            rank=rank, scale=scale,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, heads, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=not _on_tpu(),
        name="latent_attention_decode",
    )(tables.reshape(-1).astype(jnp.int32), positions.astype(jnp.int32), q, pool)


def latent_decode_attention(q, pool, tables, positions, *, rank: int, scale: float,
                            impl: str = "auto"):
    """One absorbed query a sequence over its paged latent rows.

    q: (slots, heads, width) in the pool's dtype, ``[q_lat, q_rope, 0...]``;
    pool: (blocks, block, width); tables: (slots, tmax) int32 block ids of
    that view; positions: (slots,) int32, each sequence's own position (its
    row already written): it attends rows ``0 .. position``.  Returns
    (slots, heads, rank) float32: ``softmax . c``, to be expanded by the
    value half of the up-projection."""
    if impl not in ("auto", "xla", "pallas"):
        raise ValueError(f"unknown latent attention impl {impl!r}")
    if impl == "auto":
        impl = auto_impl(pool.shape[1], pool.shape[2], rank)
    fn = latent_decode_xla if impl == "xla" else _latent_decode_pallas
    return fn(q, pool, tables, positions, rank=rank, scale=scale)


def _divisor(n: int, cap: int, interpret: bool):
    """The largest divisor of ``n`` up to ``cap`` that is whole lane tiles
    (any divisor under the interpreter); None where there is none."""
    step = 1 if interpret else 128
    return next((d for d in range(min(n, cap) // step * step, 0, -step) if n % d == 0), None)


#: keys of one SUB-TILE of a grid step's key tile, at most (the step walks
#: its tile in these: 384 read best of 128 / 256 / 384 / 768 on a v5e), and
#: heads a grid step takes, at most (a step costs about 0.3 us whatever it
#: holds: 1 / 2 / 4 / 8 heads read 2.72 / 2.60 / 2.47 / 2.42 ms a layer)
_SUB_KEYS = 384
_STEP_HEADS = 4


def _key_tile(total: int, interpret: bool):
    """Keys a grid step of the chunk kernel takes: the largest such divisor
    of the table's width up to 1,024 (768 of 17,664); None: the XLA path."""
    return _divisor(total, 1024, interpret)


def _sub_tile(tile: int, interpret: bool) -> int:
    """Keys of one sub-tile of a key tile (384 of 768).  Sub-tile edges are
    multiples of it from key 0."""
    return _divisor(tile, _SUB_KEYS, interpret)


def _lanes(stat, width: int):
    """A row statistic against ``width`` columns.  It is held replicated
    over 128 lanes, (rows, 128), and tiled from there; a (rows, 1) column
    (widths off the lane tiling: the interpreter) broadcasts by itself."""
    held = stat.shape[-1]
    return stat if held in (1, width) else pltpu.repeat(stat, width // held, axis=1)


def _chunk_flash_kernel(
    start_ref,    # scalar prefetch: (1,) int32, the chunk's first position
    q_ref,        # (heads, C, dn + dr): [q_nope | q_rope]
    kn_ref,       # (tile, heads * dn): these heads' expanded keys
    kr_ref,       # (tile, dr): the rotated key part, one for all heads
    v_ref,        # (tile, heads * dv)
    o_ref,        # (heads, C, dv) float32
    m_sc, l_sc,   # (heads, C, 128 | 1) float32: the running maximum (unscaled) and sum
    acc_sc,       # (heads, C, dv) float32
    *, tile: int, sub: int, scale: float,
):
    j = pl.program_id(1)
    heads, c_len, _ = q_ref.shape
    dn, dv = kn_ref.shape[1] // heads, v_ref.shape[1] // heads
    start = start_ref[0]
    first = j * tile
    nt = (((1,), (1,)), ((), ()))
    # (head, sub-tile) pairs in the order walked; a head's sub-tiles are
    # multiples of ``sub`` from key 0 of the table, whatever ``start`` is
    units = [(i, t * sub) for i in range(heads) for t in range(tile // sub)]

    @pl.when(j == 0)
    def _open():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def scores(i, lo, masked):
        """(C, sub) float32, UNSCALED: one product over [nope | rope]."""
        keys = pl.ds(lo, sub)
        k = jnp.concatenate([kn_ref[keys, pl.ds(i * dn, dn)], kr_ref[keys, :]], axis=1)
        s = jax.lax.dot_general(q_ref[i], k, nt, preferred_element_type=jnp.float32)
        if masked:
            q_pos = start + jax.lax.broadcasted_iota(jnp.int32, (c_len, 1), 0)
            k_pos = first + lo + jax.lax.broadcasted_iota(jnp.int32, (1, sub), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        return s

    def update(i, lo, s, carry):
        # no second select after the ``exp``: every query sees key 0 and the
        # walk starts there, so a row's maximum is finite from its first
        # sub-tile on and an unseen key's ``exp`` IS 0.  The maximum is kept
        # in the UNSCALED scores' units and the scale multiplies the
        # difference: nothing stands between the product and the select, so
        # the two paths cannot be compiled apart (a multiply there was fused
        # into the subtraction on one path and not the other: one ulp)
        m_prev, l_prev, acc = carry
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp((m_prev - m_new) * scale)
        p = jnp.exp((s - _lanes(m_new, sub)) * scale)
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        v = v_ref[pl.ds(lo, sub), pl.ds(i * dv, dv)]
        acc = acc * _lanes(alpha, dv) + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    def walk(masked):
        """Every unit of the step as ONE straight line, the NEXT unit's
        score product written before this unit's softmax: the scheduler
        keeps the order it is given, so that is what lets the MXU run
        under the ``exp``.  Masked or not, a (query, sub-tile) pair goes
        through the same operations in the same order: a seen key's select
        returns the score itself."""
        s_next = scores(*units[0], masked)
        for n, (i, lo) in enumerate(units):
            if lo == 0:
                carry = (m_sc[i], l_sc[i], acc_sc[i])
            s_now = s_next
            if n + 1 < len(units):
                s_next = scores(*units[n + 1], masked)
            carry = update(i, lo, s_now, carry)
            if lo == tile - sub:
                m_sc[i], l_sc[i], acc_sc[i] = carry

    live = first < start + c_len          # the chunk's last query sees into this tile
    whole = first + tile - 1 <= start     # and its first query sees all of it
    pl.when(jnp.logical_and(live, whole))(lambda: walk(False))
    pl.when(jnp.logical_and(live, jnp.logical_not(whole)))(lambda: walk(True))

    @pl.when(j == pl.num_programs(1) - 1)
    def _close():
        o_ref[...] = acc_sc[...] / _lanes(jnp.maximum(l_sc[...], 1e-30), dv)


def _latent_chunk_pallas(q_nope, q_rope, rows, start, w_k, w_v, *, rank, scale, tile):
    c_len, heads, dn = q_nope.shape
    dr, dv = q_rope.shape[-1], w_v.shape[-1]
    interpret = not _on_tpu()
    sub = _sub_tile(tile, interpret)
    step_heads = next(n for n in (_STEP_HEADS, 2, 1) if heads % n == 0)
    stat = 128 if sub % 128 == 0 and dv % 128 == 0 else 1
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    lat, k_r = rows[:, :rank], rows[:, rank:rank + dr]
    # every head's keys and values ONCE, as columns: (T, H * d)
    k_nope = jnp.dot(lat, w_k.astype(lat.dtype).reshape(rank, heads * dn))
    v = jnp.dot(lat, w_v.astype(lat.dtype).reshape(rank, heads * dv))
    # a tile past the chunk's end is not computed: name the last needed one
    # again, so that it is not fetched either
    last = lambda s: (s[0] + c_len - 1) // tile  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(heads // step_heads, rows.shape[0] // tile),
        in_specs=[
            pl.BlockSpec((step_heads, c_len, dn + dr), lambda h, j, s: (h, 0, 0)),
            pl.BlockSpec((tile, step_heads * dn), lambda h, j, s: (jnp.minimum(j, last(s)), h)),
            pl.BlockSpec((tile, dr), lambda h, j, s: (jnp.minimum(j, last(s)), 0)),
            pl.BlockSpec((tile, step_heads * dv), lambda h, j, s: (jnp.minimum(j, last(s)), h)),
        ],
        out_specs=pl.BlockSpec((step_heads, c_len, dv), lambda h, j, s: (h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((step_heads, c_len, stat), jnp.float32),
            pltpu.VMEM((step_heads, c_len, stat), jnp.float32),
            pltpu.VMEM((step_heads, c_len, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_chunk_flash_kernel, tile=tile, sub=sub, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((heads, c_len, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="latent_attention_chunk",
    )(jnp.reshape(start, (1,)).astype(jnp.int32),
      q.transpose(1, 0, 2), k_nope, k_r, v)
    return out.transpose(1, 0, 2)


def latent_chunk_attention(q_nope, q_rope, pool, table, positions, w_k, w_v, *,
                           rank: int, scale: float, head_group: int = 8,
                           impl: str = "auto"):
    """A chunk of ONE sequence, expanded form.  q_nope: (C, H, dn), q_rope:
    (C, H, dr); pool: (blocks, block, width); table: (tmax,) int32;
    positions: (C,) int32, CONSECUTIVE (the chunk's rows already written);
    w_k: (rank, H, dn), w_v: (rank, H, dv).  Query i attends rows ``0 ..
    positions[i]``.  ``xla``: heads go ``head_group`` at a time, so the
    scores of one group are what is live: (group, C, tmax * block) float32.
    Returns (C, H, dv) float32."""
    if impl not in ("auto", "xla", "pallas"):
        raise ValueError(f"unknown latent attention impl {impl!r}")
    c_len, heads, dn = q_nope.shape
    total = table.shape[0] * pool.shape[1]
    tile = _key_tile(total, interpret=not _on_tpu())
    if impl == "auto":
        tiles = c_len % 8 == 0 and dn % 128 == 0 and w_v.shape[-1] % 128 == 0
        impl = "pallas" if _on_tpu() and tile and tiles and rank % 128 == 0 else "xla"
    if impl == "pallas":
        if tile is None:
            raise ValueError(f"a table of {total} keys has no tile of whole lanes")
        return _latent_chunk_pallas(
            q_nope, q_rope, pool[table].reshape(-1, pool.shape[-1]), positions[0], w_k, w_v,
            rank=rank, scale=scale, tile=tile)

    rope = q_rope.shape[-1]
    group = min(head_group, heads)
    if heads % group:
        raise ValueError(f"{heads} heads do not go {group} at a time")
    rows = pool[table].reshape(-1, pool.shape[-1])              # (T * block, W)
    lat, k_r = rows[:, :rank], rows[:, rank:rank + rope]
    seen = jnp.arange(rows.shape[0])[None, None, :] <= positions[None, :, None]

    def by_group(x):
        """(.., H, d) -> (H / group, .., group, d): the scan's leading axis."""
        x = x.reshape(x.shape[:-2] + (heads // group, group, x.shape[-1]))
        return jnp.moveaxis(x, -3, 0)

    def one_group(_, inputs):
        qn, qr, wk, wv = inputs
        k = jnp.einsum("tr,rgd->tgd", lat, wk.astype(lat.dtype))
        scores = (
            jnp.einsum("cgd,tgd->gct", qn, k, preferred_element_type=jnp.float32)
            + jnp.einsum("cgd,td->gct", qr, k_r, preferred_element_type=jnp.float32)
        ) * scale
        probs = jax.nn.softmax(jnp.where(seen, scores, NEG_INF), axis=-1)
        v = jnp.einsum("tr,rgd->tgd", lat, wv.astype(lat.dtype))
        return None, jnp.einsum("gct,tgd->cgd", probs.astype(v.dtype), v,
                                preferred_element_type=jnp.float32)

    _, out = jax.lax.scan(
        one_group, None, (by_group(q_nope), by_group(q_rope), by_group(w_k), by_group(w_v)))
    return jnp.moveaxis(out, 0, 1).reshape(c_len, heads, -1)
