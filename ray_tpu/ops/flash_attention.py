"""Pallas TPU flash attention (causal) with a full custom-VJP backward.

The blockwise online-softmax formulation (Flash Attention 2) — no (seq, seq)
score matrix ever reaches HBM and no kernel instance ever holds more than one
(block_q, d) + (block_k, d) working set in VMEM, so memory is O(seq) in HBM
and O(block) in VMEM at ANY sequence length. Forward saves only out +
logsumexp per row; backward recomputes scores blockwise with two kernels
(dQ, then dK/dV). All accumulation fp32, inputs bf16/fp32.

Grid layout: ``(bh, q_block, kv_block)`` with the KV dimension minor — TPU
grids execute the minor dimension sequentially, so VMEM scratch accumulators
(acc/m/l for forward, dq / dk+dv for backward) carry across KV (resp. Q)
steps of one output block and are flushed on the block's last step.
Causally-dead (q, kv) cells are skipped with ``pl.when``; a cell the
diagonal crosses is walked in sub-tiles, and a sub-tile the mask kills
whole is never issued (``_live_tiles``); only sub-tiles the diagonal
crosses build a mask.

TPU tiling notes: per-row stats (logsumexp, delta) live in HBM as
``(bh, 8, seq)`` — value broadcast over 8 sublanes so the (sublane, lane)
block shape ``(8, block_q)`` satisfies Mosaic's (8, 128) fp32 tile
constraint. Inside the forward the running max and denominator are
``(rows, 1)`` columns, the layout a reduction over a score tile's columns
leaves them in (a change of layout a tile made the forward twice as
slow). Sequence lengths must tile by 128 on the TPU path (the public entry
raises otherwise; ``ops.attention.auto_impl`` routes such shapes to XLA).

This is the hot op behind ``ray_tpu.ops.attention.causal_attention`` — the
reference has no attention kernel of its own (user torch code runs inside
``train_loop_per_worker``); SURVEY.md §5.7 makes long-context attention a
first-class mandate for the TPU build. On non-TPU backends the same kernels
run under ``interpret=True`` so CI (virtual CPU mesh) exercises identical
code paths.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# sub-tiles: what of a grid cell is issued at all
# ---------------------------------------------------------------------------

#: Edges (q rows, kv columns) of the sub-tiles that a grid cell ON the
#: diagonal is walked in.  The grid block stays fat (one head's whole
#: sequence where it fits: the grid's per-step cost, see
#: ``flash_attention``); the causal skipping happens INSIDE it: 10 of 16
#: sub-tiles of a 1024 x 1024 cell hold a live score, the other 6 are never
#: issued.  A cell wholly below the diagonal has nothing to skip and runs
#: as ONE tile.  Measured on a v5e, ms a layer forward + dQ + dK/dV, at
#: bf16[416,1024,64] / [64,2048,64] / [16,4096,64] (PERF.md section 6,
#: PR 44): 256 x 256 3.79 / 2.69 / 2.13; 128 x 256 3.91 / 2.80 / 2.19;
#: 256 x 512 4.27 / 2.77 / 2.17; 512 x 512 4.29 / 2.78 / 2.18;
#: 256 x 128 4.38 / 2.95 / 2.26 and 128 x 128 4.76 / 3.03 / 2.30 (products
#: too small to keep an MXU's weights loaded); no sub-tiles 5.37 / 2.84 /
#: 2.21; before PR 44 7.03 / 3.49 / 2.73.  Head width 64; no other width
#: has been measured.
_SUB_Q, _SUB_K = 256, 256


def _sub_tiles(block_q: int, block_k: int) -> tuple[int, int]:
    """The sub-tile of a grid block: the constants above where they divide
    the block, else the largest edge that does (a block smaller than a
    sub-tile is one sub-tile)."""
    return math.gcd(block_q, _SUB_Q), math.gcd(block_k, _SUB_K)


def _diag_offsets(block_q: int, block_k: int) -> list[int]:
    """Every ``q_start - k_start`` of a grid cell the diagonal crosses: the
    cell holds a live score (``k_start <= q_start + block_q - 1``) AND a dead
    one (``k_start + block_k - 1 > q_start``).  A cell further below the
    diagonal is live whole, one further above it is dead whole."""
    g = math.gcd(block_q, block_k)  # every offset is a multiple of it
    return list(range(g - block_q, block_k - 1, g))


def _live_tiles(block_q, block_k, sub_q, sub_k, off):
    """``(i, j, crossed)`` of every sub-tile of one grid cell that holds a
    live score, row-major: the products a kernel body issues.  ``off`` is
    the cell's ``q_start - k_start`` where the diagonal crosses it and None
    for a cell wholly below the diagonal; ``crossed`` says the sub-tile also
    holds a dead score and needs the mask."""
    tiles = []
    for i in range(block_q // sub_q):
        for j in range(block_k // sub_k):
            if off is None:
                tiles.append((i, j, False))
                continue
            r0, c0 = off + i * sub_q, j * sub_k  # first row, first column
            if c0 <= r0 + sub_q - 1:             # else dead whole: never issued
                tiles.append((i, j, c0 + sub_k - 1 > r0))
    return tiles


def _walk_cell(q_start, k_start, block_q, block_k, seq, walk):
    """Run ``walk`` for the ONE case this grid cell is, decided by
    ``program_id`` alone: ``walk(off, sub_q, sub_k)`` where the diagonal
    crosses it at the static offset ``off``, in sub-tiles;
    ``walk(None, block_q, block_k)`` where it is live whole, as one tile.
    A dead cell runs nothing.  A case that no cell of this grid can be
    (``seq`` is static) is not traced at all: where the sequence is one
    block, the diagonal cell is the only one."""
    off = q_start - k_start
    for o in _diag_offsets(block_q, block_k):
        if block_k - seq <= o <= seq - block_q:
            pl.when(off == o)(functools.partial(walk, o, *_sub_tiles(block_q, block_k)))
    if seq - block_q >= block_k - 1:
        pl.when(off >= block_k - 1)(functools.partial(walk, None, block_q, block_k))


def _tile_mask(shape, diag, transposed=False):
    """col <= row inside a sub-tile whose first row stands ``diag`` below
    its first column (``diag = off + i * sub_q - j * sub_k``, static):
    (sub_q, sub_k), or (sub_k, sub_q) for a tile of transposed scores."""
    q_axis = 1 if transposed else 0
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return cols - rows <= diag


def _by(tiles, axis):
    """Group ``_live_tiles`` by q sub-tile (axis 0) or kv sub-tile (axis 1):
    ``{outer: [(inner, crossed), ...]}`` in issue order."""
    groups: dict[int, list] = {}
    for t in tiles:
        groups.setdefault(t[axis], []).append((t[1 - axis], t[2]))
    return groups


def _f32_rows(ref, sub):
    """``load(n)``: sub-tile ``n`` of a (1, block, d) ref as float32, made
    once however many sub-tiles of the other axis meet it."""
    return functools.cache(lambda n: ref[0, pl.ds(n * sub, sub), :].astype(jnp.float32))


_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


# One sub-tile's work is a jitted function of VALUES: jax traces it once a
# (shape, ``diag``) and a kernel body that issues it ten times holds ten
# calls, not ten copies (the kernels are traced four times a train step, and
# unrolled copies cost the cell 2 s of set-up).  Mosaic inlines the calls.
# ``diag`` is None for a sub-tile the diagonal does not cross: no mask.


@functools.partial(jax.jit, static_argnames=("diag",))
def _fwd_tile(q, k, v, m, l, acc, *, diag):
    """The online softmax of a q sub-tile over one more kv sub-tile."""
    s = _dot(q, k, _NT)  # (sub_q, sub_k)
    if diag is not None:
        s = jnp.where(_tile_mask(s.shape, diag), s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l = l * corr + p.sum(axis=1, keepdims=True)
    return m_new, l, acc * corr + _dot(p, v, _NN)


@functools.partial(jax.jit, static_argnames=("scale", "diag", "transposed"))
def _p_and_ds(q, k, v, do, lse, delta, *, scale, diag, transposed=False):
    """One sub-tile's softmax weights and score gradients, recomputed from
    the forward's row statistics: (sub_q, sub_k) with ``lse`` / ``delta`` as
    (sub_q, 1) columns, or TRANSPOSED, (sub_k, sub_q) with them as
    (1, sub_q) rows: the same products, element for element."""
    if transposed:
        s, dp = _dot(k, q, _NT), _dot(v, do, _NT)
    else:
        s, dp = _dot(q, k, _NT), _dot(do, v, _NT)
    p = jnp.exp(scale * s - lse)
    if diag is not None:
        p = jnp.where(_tile_mask(p.shape, diag, transposed), p, 0.0)
    return p, p * (dp - delta) * scale


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *, scale, seq):
    """Grid (bh, qi, kj), kj minor/sequential. Scratch carries the online
    softmax state across kj steps of one q block; inside a step it is held
    in values, a q sub-tile at a time, across the kv sub-tiles it meets.
    The row statistics are (rows, 1) columns throughout (the layout a
    reduction over a score tile's columns leaves them in): the one change
    of layout is ``lse``'s, into the lanes of its output, once a q block.
    Where the kv grid has ONE step (``seq == block_k``) the state never
    touches the scratch."""
    qi, kj = pl.program_id(1), pl.program_id(2)
    block_q, block_k, d = q_ref.shape[1], k_ref.shape[1], q_ref.shape[2]
    q_start = qi * block_q
    k_start = kj * block_k
    j_last = (q_start + block_q - 1) // block_k  # last causally-live kv block
    one_step = seq == block_k

    def finish(rows, m, l, acc):
        l = jnp.maximum(l, 1e-30)
        o_ref[0, rows, :] = (acc / l).astype(o_ref.dtype)
        lse = (m + jnp.log(l)).T  # (1, rows): rows into lanes
        lse_ref[0, :, rows] = jnp.broadcast_to(lse, (lse_ref.shape[1], lse.shape[1]))

    def walk(off, sub_q, sub_k):
        k_of, v_of = _f32_rows(k_ref, sub_k), _f32_rows(v_ref, sub_k)
        for i, kv_tiles in _by(_live_tiles(block_q, block_k, sub_q, sub_k, off), 0).items():
            rows = pl.ds(i * sub_q, sub_q)
            q = q_ref[0, rows, :].astype(jnp.float32) * scale
            if one_step:
                m = jnp.full((sub_q, 1), NEG_INF, jnp.float32)
                l = jnp.zeros((sub_q, 1), jnp.float32)
                acc = jnp.zeros((sub_q, d), jnp.float32)
            else:
                m, l, acc = m_sc[rows, :], l_sc[rows, :], acc_sc[rows, :]
            for j, crossed in kv_tiles:
                diag = off + i * sub_q - j * sub_k if crossed else None
                m, l, acc = _fwd_tile(q, k_of(j), v_of(j), m, l, acc, diag=diag)
            if one_step:
                finish(rows, m, l, acc)
            else:
                m_sc[rows, :], l_sc[rows, :], acc_sc[rows, :] = m, l, acc

    if one_step:
        return _walk_cell(q_start, k_start, block_q, block_k, seq, walk)

    @pl.when(kj == 0)
    def _():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    _walk_cell(q_start, k_start, block_q, block_k, seq, walk)

    @pl.when(kj == j_last)
    def _():
        finish(slice(None), m_sc[:], l_sc[:], acc_sc[:])


def _flash_fwd(q, k, v, *, block_q, block_k):
    bh, seq, d = q.shape
    scale = 1.0 / (d**0.5)
    grid = (bh, seq // block_q, seq // block_k)
    out, lse8 = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, seq=seq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 8, seq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max, a column
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
        ],
        interpret=_interpret(),
        name="flash_fwd",
    )(q, k, v)
    return out, lse8[:, :1, :]  # (bh, 1, seq)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_sc, *, scale, seq):
    qi, kj = pl.program_id(1), pl.program_id(2)
    block_q, block_k, d = q_ref.shape[1], k_ref.shape[1], q_ref.shape[2]
    q_start, k_start = qi * block_q, kj * block_k
    j_last = (q_start + block_q - 1) // block_k
    one_step = seq == block_k  # ONE kv step: dq never touches the scratch

    def walk(off, sub_q, sub_k):
        k_of, v_of = _f32_rows(k_ref, sub_k), _f32_rows(v_ref, sub_k)
        for i, kv_tiles in _by(_live_tiles(block_q, block_k, sub_q, sub_k, off), 0).items():
            rows = pl.ds(i * sub_q, sub_q)
            q, do = q_ref[0, rows, :].astype(jnp.float32), do_ref[0, rows, :].astype(jnp.float32)
            lse, delta = lse_ref[0, 0, rows][:, None], delta_ref[0, 0, rows][:, None]  # columns
            dq = jnp.zeros((sub_q, d), jnp.float32) if one_step else dq_sc[rows, :]
            for j, crossed in kv_tiles:
                diag = off + i * sub_q - j * sub_k if crossed else None
                _, ds = _p_and_ds(q, k_of(j), v_of(j), do, lse, delta, scale=scale, diag=diag)
                dq = dq + _dot(ds, k_of(j), _NN)
            if one_step:
                dq_ref[0, rows, :] = dq.astype(dq_ref.dtype)
            else:
                dq_sc[rows, :] = dq

    if one_step:
        return _walk_cell(q_start, k_start, block_q, block_k, seq, walk)

    @pl.when(kj == 0)
    def _():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    _walk_cell(q_start, k_start, block_q, block_k, seq, walk)

    @pl.when(kj == j_last)
    def _():
        dq_ref[0] = dq_sc[:].astype(dq_ref.dtype)


def _dkv_kernel(
    k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_sc, dv_sc, *, scale, seq
):
    """Grid (bh, kb, qi), qi minor/sequential; accumulates dk/dv for one kv
    block across its causally-live q blocks, a kv sub-tile at a time in
    values across the q sub-tiles it meets inside a step.  The scores are
    made TRANSPOSED, (sub_k, sub_q): ``lse`` and ``delta`` then broadcast
    from the lanes they are stored in, and ``p^T do`` / ``ds^T q`` are
    plain products with no transpose of a score tile."""
    kb, qi = pl.program_id(1), pl.program_id(2)
    block_k, block_q, d = k_ref.shape[1], q_ref.shape[1], k_ref.shape[2]
    k_start, q_start = kb * block_k, qi * block_q
    i_first = k_start // block_q     # first q block the diagonal touches
    n_q = pl.num_programs(2)
    one_step = seq == block_q  # ONE q step: dk, dv never touch the scratch

    def walk(off, sub_q, sub_k):
        q_of, do_of = _f32_rows(q_ref, sub_q), _f32_rows(do_ref, sub_q)
        for j, q_tiles in _by(_live_tiles(block_q, block_k, sub_q, sub_k, off), 1).items():
            cols = pl.ds(j * sub_k, sub_k)
            k, v = k_ref[0, cols, :].astype(jnp.float32), v_ref[0, cols, :].astype(jnp.float32)
            if one_step:
                dk = dv = jnp.zeros((sub_k, d), jnp.float32)
            else:
                dk, dv = dk_sc[cols, :], dv_sc[cols, :]
            for i, crossed in q_tiles:
                rows = pl.ds(i * sub_q, sub_q)
                diag = off + i * sub_q - j * sub_k if crossed else None
                p, ds = _p_and_ds(  # (sub_k, sub_q); lse, delta: (1, sub_q) rows
                    q_of(i), k, v, do_of(i), lse_ref[0, :, rows], delta_ref[0, :, rows],
                    scale=scale, diag=diag, transposed=True,
                )
                dv = dv + _dot(p, do_of(i), _NN)
                dk = dk + _dot(ds, q_of(i), _NN)
            if one_step:
                dk_ref[0, cols, :] = dk.astype(dk_ref.dtype)
                dv_ref[0, cols, :] = dv.astype(dv_ref.dtype)
            else:
                dk_sc[cols, :], dv_sc[cols, :] = dk, dv

    if one_step:
        return _walk_cell(q_start, k_start, block_q, block_k, seq, walk)

    @pl.when(qi == i_first)
    def _():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    _walk_cell(q_start, k_start, block_q, block_k, seq, walk)

    @pl.when(qi == n_q - 1)
    def _():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, do, *, block_q, block_k):
    bh, seq, d = q.shape
    scale = 1.0 / (d**0.5)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)  # (bh, seq)
    delta = delta[:, None, :]  # (bh, 1, seq)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, seq=seq),
        grid=(bh, seq // block_q, seq // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, seq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, seq=seq),
        grid=(bh, seq // block_k, seq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, kk, i: (b, kk, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, kk, i: (b, kk, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, kk, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, kk, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, kk, i: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, kk, i: (b, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, kk, i: (b, kk, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, kk, i: (b, kk, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d), k.dtype),
            jax.ShapeDtypeStruct((bh, seq, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(k, v, q, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------


def _pick_blocks(seq: int, block_q: int, block_k: int) -> tuple[int, int]:
    bq = min(block_q, seq)
    bk = min(block_k, seq)
    while seq % bq:
        bq //= 2
    while seq % bk:
        bk //= 2
    return max(bq, 1), max(bk, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_core(q, k, v, block_q, block_k, block_q_bwd, block_k_bwd):
    out, _ = _flash_fwd(q, k, v, block_q=block_q, block_k=block_k)
    return out


def _flash_core_fwd(q, k, v, block_q, block_k, block_q_bwd, block_k_bwd):
    out, lse = _flash_fwd(q, k, v, block_q=block_q, block_k=block_k)
    # Name the kernel's own residuals so a jax.checkpoint policy
    # (save_only_these_names, models/gpt.py remat_policy="attn"/"big") can
    # keep exactly these and dead-code the whole forward kernel out of the
    # rematerialized backward — the single biggest recompute in a
    # full-remat transformer block.
    from jax.ad_checkpoint import checkpoint_name

    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, out, lse)


def _flash_core_bwd(block_q, block_k, block_q_bwd, block_k_bwd, res, do):
    q, k, v, out, lse = res
    return _flash_bwd(q, k, v, out, lse, do, block_q=block_q_bwd, block_k=block_k_bwd)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    block_q: int | None = None,
    block_k: int | None = None,
    block_q_bwd: int | None = None,
    block_k_bwd: int | None = None,
) -> jax.Array:
    """Causal flash attention. q,k,v: (batch, heads, seq, head_dim).

    O(seq) HBM / O(block) VMEM; differentiable (custom VJP with
    blockwise-recompute backward). Forward and backward grid blocks may
    differ (the dQ/dKV kernels have different reuse patterns than the
    forward). On TPU the blocks must tile by 128 (Mosaic lane constraint) —
    anything else raises; interpret mode (CPU CI) accepts any
    power-of-two-friendly blocking.
    """
    b, h, s, d = q.shape
    # Grid blocks of 1024×1024 measured fastest on v5e at (bh 256, s 1024,
    # d 64): fewer, fatter grid steps win — the kernel is latency-bound per
    # step at small head_dim, not VMEM-bound (sweep: 4.1 ms/layer at 256×512
    # → 2.6 ms at 1024×1024). The causally dead part of a fat block is
    # skipped inside the step, in sub-tiles (``_sub_tiles``). _pick_blocks
    # clamps to the actual sequence length.
    block_q = block_q if block_q is not None else 1024
    block_k = block_k if block_k is not None else 1024
    block_q_bwd = block_q_bwd if block_q_bwd is not None else block_q
    block_k_bwd = block_k_bwd if block_k_bwd is not None else block_k
    bq, bk = _pick_blocks(s, block_q, block_k)
    bqb, bkb = _pick_blocks(s, block_q_bwd, block_k_bwd)
    if not _interpret() and (bq % 128 or bk % 128 or bqb % 128 or bkb % 128):
        # never a silent change of implementation: ``auto`` callers are
        # routed by ``ops.attention.auto_impl`` before they get here
        raise ValueError(
            f"flash attention on TPU needs blocks that tile by 128 (Mosaic "
            f"lane constraint); seq={s} picked fwd {bq}x{bk}, bwd {bqb}x{bkb} "
            "— use impl='xla' for this shape"
        )
    merge = lambda t: t.reshape(b * h, s, d)  # noqa: E731
    out = _flash_core(merge(q), merge(k), merge(v), bq, bk, bqb, bkb)
    return out.reshape(b, h, s, d)


def flash_shardable(batch: int, heads: int, mesh) -> bool:
    """True when (batch, heads) divide the mesh's (dp*fsdp, tp) axes — the
    precondition for ``flash_attention_sharded``."""
    dp = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
    tp = mesh.shape.get("tp", 1)
    return batch % dp == 0 and heads % tp == 0


def flash_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array, mesh) -> jax.Array:
    """Flash attention inside a dp/fsdp/tp-sharded pjit program.

    A bare ``pallas_call`` has no GSPMD partitioning rule, so calling
    ``flash_attention`` directly under a multi-device pjit makes XLA
    all-gather q/k/v and replicate the kernel on every chip. This wrapper
    shard_maps it — batch over (dp, fsdp), heads over tp, seq/head_dim local
    — so each chip runs the kernel on exactly its shard (attention has no
    cross-batch/cross-head communication). Callers must check
    ``flash_shardable`` first.
    """
    from jax.sharding import PartitionSpec as P

    b, h, s, d = q.shape
    if not flash_shardable(b, h, mesh):
        raise ValueError(
            f"batch {b} / heads {h} don't divide mesh axes "
            f"dp*fsdp={mesh.shape.get('dp', 1) * mesh.shape.get('fsdp', 1)}, "
            f"tp={mesh.shape.get('tp', 1)}"
        )
    spec = P(("dp", "fsdp"), "tp", None, None)
    fn = jax.shard_map(
        flash_attention, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
