"""Pallas TPU flash attention (causal) with a full custom-VJP backward.

The blockwise online-softmax formulation (Flash Attention 2) — no (seq, seq)
score matrix ever reaches HBM, so memory is O(seq) in HBM; the forward holds
one (block_q, width) + (block_k, width) working set in VMEM at any sequence
length. Forward saves only out + logsumexp per row; the backward recomputes
scores blockwise in ONE kernel: a live sub-tile's softmax weights and score
gradients are made once (2 products, one ``exp`` pass) and feed dv, dk and
dq together (3 products), where a dQ kernel and a dK/dV kernel made every
tile's scores twice (3 + 4 products, two ``exp`` passes).  Its price is dq
of a head block's whole sequence in VMEM (seq x block width float32: 0.5 MB
at 1,024 x 128 lanes, 4 MB at 8k) across the kv blocks, asked for from the
shapes (``_bwd_vmem_bytes``). All accumulation fp32, inputs bf16/fp32.

Operand layout: the kernels index heads as COLUMN blocks of
``(batch, seq, heads x head_dim)`` arrays, the layout a projection writes
and the next product reads, so a transformer block moves no head around:
``flash_attention_packed`` reads q, k and v out of the fused projection's
own ``(batch, seq, 3 x heads x head_dim)`` output (three ``BlockSpec``s over
ONE array) and writes ``(batch, seq, heads x head_dim)``; its backward reads
``do`` and writes dq, dk, dv in the same column blocks.  A head-major
``(batch x heads, seq, 64)`` operand would cost a transposing copy of every
operand on each side of every kernel (16 or so a layer of a train step, a
third of the layer outside the kernels), and a 64-wide minor dimension is
half a 128-lane tile: such an array takes twice its bytes in HBM and every
fetch of it is half empty.  A column block is ``_heads_per_block`` heads
wide: TWO heads at ``head_dim`` 64 (128 lanes), one at 128, one block of 256
lanes at 256.  A grid step serves every head of its block with the SAME
products a head alone would issue: the queries (in the backward the keys and
values) of one head at a time with the other heads' lanes zeroed, contracted
over the block's whole width (a 64-deep product fills half the MXU's depth
anyway), each head's softmax its own; ``p @ v`` over the whole block is
right in that head's lanes, and ``_merge_heads`` takes them.

Grid layout: ``(batch, head block, q_block, kv_block)`` with the KV
dimension minor — TPU grids execute the minor dimension sequentially, so
VMEM scratch accumulators (acc/m/l for forward) carry across KV steps of
one output block and are flushed on the block's last step; the backward's
grid is ``(batch, head block, kv_block, q_block)``, dk+dv carried across
the Q steps of a kv block and dq across the kv blocks (``_bwd_kernel``).  Causally-dead (q, kv) cells are skipped with
``pl.when``; a cell the diagonal crosses is walked in sub-tiles, and a
sub-tile the mask kills whole is never issued (``_live_tiles``); only
sub-tiles the diagonal crosses build a mask.

TPU tiling notes: the per-row logsumexp lives in HBM as ``(batch, head
blocks, heads a block, seq)``, rows in the lanes, one sublane a head of the
block; the backward's delta = rowsum(do * out) lies the same way in its
scratch and never reaches HBM.  Inside the forward the running max and
denominator are ``(rows, 1)`` columns, the layout a reduction over a score
tile's columns leaves them in (a change of layout a tile made the forward
twice as slow). Sequence lengths must tile by 128 on the TPU path and a
column block must be whole 128-lane tiles (the public entries raise
otherwise; ``ops.attention.auto_impl`` routes such shapes to XLA).

This is the hot op behind ``ray_tpu.ops.attention.causal_attention`` and
``models.gpt``'s block — the reference has no attention kernel of its own
(user torch code runs inside ``train_loop_per_worker``); SURVEY.md §5.7
makes long-context attention a first-class mandate for the TPU build. On
non-TPU backends the same kernels run under ``interpret=True`` so CI
(virtual CPU mesh) exercises identical code paths.
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
    """``load(n)``: sub-tile ``n`` of a (1, block, width) ref as float32, made
    once however many sub-tiles of the other axis (and heads) meet it."""
    return functools.cache(lambda n: ref[0, pl.ds(n * sub, sub), :].astype(jnp.float32))


# ---------------------------------------------------------------------------
# heads of one column block
# ---------------------------------------------------------------------------


def _heads_per_block(heads: int, head_dim: int) -> int:
    """Heads side by side in one column block: as many as fill a 128-lane
    tile and divide the head count (two at ``head_dim`` 64), one where a
    head is 128 lanes or wider.  The block is ``_heads_per_block x
    head_dim`` lanes wide; on a TPU that must be whole tiles (``_blocks``)."""
    return math.gcd(heads, max(1, 128 // head_dim))


def _own_lanes(x, g, hd):
    """``x`` (rows, block width) with every lane outside head ``g``'s
    zeroed: contracted over the whole width, it meets head ``g``'s lanes of
    the other operand alone."""
    if x.shape[1] == hd:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= g * hd) & (lane < (g + 1) * hd), x, 0.0)


def _merge_heads(parts, hd):
    """Lanes of head ``g`` from ``parts[g]``, side by side: a product over
    the block's whole width is right in its own head's lanes only."""
    out = parts[0]
    for g in range(1, len(parts)):
        lane = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
        out = jnp.where(lane >= g * hd, parts[g], out)
    return out


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


@functools.partial(jax.jit, static_argnames=("scale", "diag"))
def _p_and_ds(q, k, v, do, lse, delta, *, scale, diag):
    """One sub-tile's softmax weights and score gradients, recomputed from
    the forward's row statistics, TRANSPOSED: (sub_k, sub_q), with ``lse``
    and ``delta`` as the (1, sub_q) rows they are stored as."""
    s, dp = _dot(k, q, _NT), _dot(v, do, _NT)
    p = jnp.exp(scale * s - lse)
    if diag is not None:
        p = jnp.where(_tile_mask(p.shape, diag, transposed=True), p, 0.0)
    return p, p * (dp - delta) * scale


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *, scale, seq, hd):
    """Grid (batch, head block, qi, kj), kj minor/sequential. Scratch
    carries each head's online softmax state across kj steps of one q block;
    inside a step it is held in values, a q sub-tile and a head at a time,
    across the kv sub-tiles it meets.  The row statistics are (rows, 1)
    columns throughout (the layout a reduction over a score tile's columns
    leaves them in): the one change of layout is ``lse``'s, into the lanes
    of its output, once a q block.  Where the kv grid has ONE step
    (``seq == block_k``) the state never touches the scratch."""
    qi, kj = pl.program_id(2), pl.program_id(3)
    block_q, block_k, width = q_ref.shape[1], k_ref.shape[1], q_ref.shape[2]
    n_heads = width // hd
    q_start = qi * block_q
    k_start = kj * block_k
    j_last = (q_start + block_q - 1) // block_k  # last causally-live kv block
    one_step = seq == block_k

    def finish(rows, states):
        outs = []
        for g, (m, l, acc) in enumerate(states):
            l = jnp.maximum(l, 1e-30)
            outs.append(acc / l)
            lse_ref[0, 0, pl.ds(g, 1), rows] = (m + jnp.log(l)).T  # (1, rows): rows into lanes
        o_ref[0, rows, :] = _merge_heads(outs, hd).astype(o_ref.dtype)

    def walk(off, sub_q, sub_k):
        k_of, v_of = _f32_rows(k_ref, sub_k), _f32_rows(v_ref, sub_k)
        for i, kv_tiles in _by(_live_tiles(block_q, block_k, sub_q, sub_k, off), 0).items():
            rows = pl.ds(i * sub_q, sub_q)
            q_all = q_ref[0, rows, :].astype(jnp.float32) * scale
            states = []
            for g in range(n_heads):
                q = _own_lanes(q_all, g, hd)
                if one_step:
                    m = jnp.full((sub_q, 1), NEG_INF, jnp.float32)
                    l = jnp.zeros((sub_q, 1), jnp.float32)
                    acc = jnp.zeros((sub_q, width), jnp.float32)
                else:
                    m, l, acc = m_sc[g, rows, :], l_sc[g, rows, :], acc_sc[g, rows, :]
                for j, crossed in kv_tiles:
                    diag = off + i * sub_q - j * sub_k if crossed else None
                    m, l, acc = _fwd_tile(q, k_of(j), v_of(j), m, l, acc, diag=diag)
                if one_step:
                    states.append((m, l, acc))
                else:
                    m_sc[g, rows, :], l_sc[g, rows, :], acc_sc[g, rows, :] = m, l, acc
            if one_step:
                finish(rows, states)

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
        finish(slice(None), [(m_sc[g], l_sc[g], acc_sc[g]) for g in range(n_heads)])


def _col_spec(block, width, first, seq_axis):
    """A (1, block, width) block of a (batch, seq, columns) array: the
    sequence block the grid's ``seq_axis`` names (2 or 3), and the column
    block ``first`` + the grid's head block."""
    return pl.BlockSpec((1, block, width), lambda *ids: (ids[0], ids[seq_axis], first + ids[1]))


def _stat_spec(n_heads, block_q, q_axis):
    """One head block's rows of a (batch, head blocks, heads a block, seq)
    statistic."""
    return pl.BlockSpec((1, 1, n_heads, block_q), lambda *ids: (ids[0], ids[1], 0, ids[q_axis]))


def _flash_fwd(q, k, v, first, heads, hd, *, block_q, block_k):
    """``q``, ``k``, ``v``: (batch, seq, columns) arrays (one array three
    times where the projection is fused) whose heads start at the column
    blocks ``first``."""
    b, seq, _ = q.shape
    n = _heads_per_block(heads, hd)
    width = n * hd
    scale = 1.0 / (hd**0.5)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, seq=seq, hd=hd),
        grid=(b, heads // n, seq // block_q, seq // block_k),
        in_specs=[
            _col_spec(block_q, width, first[0], 2),
            _col_spec(block_k, width, first[1], 3),
            _col_spec(block_k, width, first[2], 3),
        ],
        out_specs=[_col_spec(block_q, width, 0, 2), _stat_spec(n, block_q, 2)],
        out_shape=[
            jax.ShapeDtypeStruct((b, seq, heads * hd), q.dtype),
            jax.ShapeDtypeStruct((b, heads // n, n, seq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, block_q, 1), jnp.float32),      # running max, a column a head
            pltpu.VMEM((n, block_q, 1), jnp.float32),      # running denom
            pltpu.VMEM((n, block_q, width), jnp.float32),  # output accumulators
        ],
        interpret=_interpret(),
        name="flash_fwd",
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _when(always, cond):
    """``pl.when(cond)``; a plain call where the grid makes ``cond`` hold in
    every step (``always``, static), so that a kernel of ONE step stays one
    straight run of code the scheduler can overlap from end to end."""
    return (lambda body: body()) if always else pl.when(cond)


def _bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, out_ref,
    dq_sc, delta_sc, dk_sc, dv_sc, dq_out, dk_out, dv_out, sems, *, scale, seq, hd,
):
    """dq, dk AND dv.  Grid (batch, head block, kb, qi), qi minor/sequential.
    A live (q sub-tile, kv sub-tile, head) makes its softmax weights and
    score gradients ONCE (``_p_and_ds``: 2 products) and feeds all three
    gradients from them (3 products).

    The score tile is TRANSPOSED, (sub_k, sub_q), and never changes layout
    (a layout change a tile doubled the forward): ``lse`` and ``delta``
    broadcast from the lanes they are stored in, ``dv += p^T do`` and ``dk
    += ds^T q`` are plain products, and dq is accumulated transposed too,
    ``dq^T += k^T ds^T``, a plain product whose left operand is turned once
    a kv sub-tile.  dk and dv of one kv block are summed over its causally
    live q blocks, a kv sub-tile and a head at a time in values across the
    q sub-tiles it meets (``dk_sc`` / ``dv_sc`` between grid steps); dq^T of
    the head block's WHOLE sequence stays in ``dq_sc`` (q blocks, width,
    block_q) across the kv blocks: the other head's rows of ``k^T`` are
    zero, so the heads of a block share one accumulator and nothing is
    merged.  A q block's dq is complete in the step of its last live kv
    block and is turned once there.  ``delta`` = rowsum(do * out) a head is
    made in a q block's first step (kv block 0 is live for every q block)
    and kept in ``delta_sc``, rows in the lanes.

    ``out_ref`` is the whole (batch, seq, 3 x heads x head_dim) gradient in
    HBM: a finished block leaves through a staging buffer of two slots
    (``dq_out``, ``dk_out``, ``dv_out``) by a copy of its own into dq's,
    dk's or dv's column block, three column blocks of one array from one
    kernel, so the gradient of a fused projection is never concatenated.  A
    slot's copy is waited for at the top of the step that fills the slot
    again (two finished blocks later), and at the grid's last step."""
    bi, h, kb, qi = (pl.program_id(a) for a in range(4))
    block_k, block_q, width = k_ref.shape[1], q_ref.shape[1], k_ref.shape[2]
    n_heads = width // hd
    n_k, n_q = seq // block_k, seq // block_q
    part = out_ref.shape[2] // (3 * width)  # column blocks of one of dq, dk, dv
    k_start, q_start = kb * block_k, qi * block_q
    i_first = k_start // block_q                # first q block the diagonal touches
    j_last = (q_start + block_q - 1) // block_k  # last causally-live kv block
    one_q, one_k = n_q == 1, n_k == 1

    # -- finished blocks on their way out ------------------------------------
    q_done, kv_done = kb == j_last, qi == n_q - 1
    head_block = bi * pl.num_programs(1) + h
    leaving = {  # which gradient: (staging buffer, its rows of the sequence, blocks sent so far)
        0: (dq_out, q_start, head_block * n_q + qi),
        1: (dk_out, k_start, head_block * n_k + kb),
        2: (dv_out, k_start, head_block * n_k + kb),
    }

    def slot_of(which):
        return leaving[which][2] % 2

    def copy_out(which, slot=None):
        stage, start, _ = leaving[which]
        slot = slot_of(which) if slot is None else slot
        cols = pl.ds(pl.multiple_of((which * part + h) * width, width), width)
        rows = pl.ds(pl.multiple_of(start, stage.shape[1]), stage.shape[1])
        return pltpu.make_async_copy(
            stage.at[slot], out_ref.at[bi, rows, cols], sems.at[which, slot])

    @pl.when(kv_done & (leaving[1][2] >= 2))
    def _():
        copy_out(1).wait()
        copy_out(2).wait()

    @pl.when(q_done & (leaving[0][2] >= 2))
    def _():
        copy_out(0).wait()

    # -- a q block's first step: its delta, and dq^T from zero -----------------
    @_when(one_k, kb == 0)
    def _():
        dq_sc[qi] = jnp.zeros(dq_sc.shape[1:], jnp.float32)
        sub_q = _sub_tiles(block_q, block_k)[0]
        for i in range(block_q // sub_q):
            rows = pl.ds(i * sub_q, sub_q)
            do_all = do_ref[0, rows, :].astype(jnp.float32)
            o_all = o_ref[0, rows, :].astype(jnp.float32)
            for g in range(n_heads):  # a column, turned into the lanes once a q sub-tile
                delta = (_own_lanes(do_all, g, hd) * o_all).sum(axis=1, keepdims=True)
                delta_sc[qi, pl.ds(g, 1), rows] = delta.T

    # -- the cell's live sub-tiles ---------------------------------------------
    def walk(off, sub_q, sub_k):
        q_of, do_of = _f32_rows(q_ref, sub_q), _f32_rows(do_ref, sub_q)
        for j, q_tiles in _by(_live_tiles(block_q, block_k, sub_q, sub_k, off), 1).items():
            cols = pl.ds(j * sub_k, sub_k)
            k_all = k_ref[0, cols, :].astype(jnp.float32)
            v_all = v_ref[0, cols, :].astype(jnp.float32)
            dks, dvs = [], []
            for g in range(n_heads):
                k, v = _own_lanes(k_all, g, hd), _own_lanes(v_all, g, hd)
                k_t = k.T  # (width, sub_k): head g's rows, the others zero
                if one_q:
                    dk = dv = jnp.zeros((sub_k, width), jnp.float32)
                else:
                    dk, dv = dk_sc[g, cols, :], dv_sc[g, cols, :]
                for i, crossed in q_tiles:
                    rows = pl.ds(i * sub_q, sub_q)
                    diag = off + i * sub_q - j * sub_k if crossed else None
                    p, ds = _p_and_ds(  # (sub_k, sub_q); lse, delta: (1, sub_q) rows
                        q_of(i), k, v, do_of(i),
                        lse_ref[0, 0, pl.ds(g, 1), rows], delta_sc[qi, pl.ds(g, 1), rows],
                        scale=scale, diag=diag,
                    )
                    dv = dv + _dot(p, do_of(i), _NN)
                    dk = dk + _dot(ds, q_of(i), _NN)
                    dq_sc[qi, :, rows] += _dot(k_t, ds, _NN)  # (width, sub_q): in kv order
                if one_q:
                    dks.append(dk)
                    dvs.append(dv)
                else:
                    dk_sc[g, cols, :], dv_sc[g, cols, :] = dk, dv
            if one_q:
                dk_out[slot_of(1), cols, :] = _merge_heads(dks, hd).astype(dk_out.dtype)
                dv_out[slot_of(2), cols, :] = _merge_heads(dvs, hd).astype(dv_out.dtype)

    if not one_q:
        @pl.when(qi == i_first)
        def _():
            dk_sc[:] = jnp.zeros_like(dk_sc)
            dv_sc[:] = jnp.zeros_like(dv_sc)

    _walk_cell(q_start, k_start, block_q, block_k, seq, walk)

    # -- what this step finished leaves ----------------------------------------
    @_when(one_q, kv_done)
    def _():
        if not one_q:
            heads_of = lambda sc: [sc[g] for g in range(n_heads)]  # noqa: E731
            dk_out[slot_of(1)] = _merge_heads(heads_of(dk_sc), hd).astype(dk_out.dtype)
            dv_out[slot_of(2)] = _merge_heads(heads_of(dv_sc), hd).astype(dv_out.dtype)
        copy_out(1).start()
        copy_out(2).start()

    @_when(one_k, q_done)
    def _():
        sub_q = _sub_tiles(block_q, block_k)[0]
        for i in range(block_q // sub_q):  # turned once, a sub-tile at a time
            rows = pl.ds(i * sub_q, sub_q)
            dq_out[slot_of(0), rows, :] = dq_sc[qi, :, rows].T.astype(dq_out.dtype)
        copy_out(0).start()

    last = (bi == pl.num_programs(0) - 1) & (h == pl.num_programs(1) - 1) & kv_done & (kb == n_k - 1)

    @pl.when(last)
    def _():
        for which in range(3):
            copy_out(which).wait()
            if pl.num_programs(0) * pl.num_programs(1) * (n_k if which else n_q) > 1:
                copy_out(which, 1 - slot_of(which)).wait()  # the block before the last


#: What Mosaic gives a kernel when it is asked for nothing, and the most the
#: backward asks for: a v5e's core holds 128 MiB.
_VMEM_DEFAULT, _VMEM_MOST = 16 * 2**20, 100 * 2**20


def _bwd_vmem_bytes(seq, width, n, block_q, block_k, itemsize):
    """The fast memory ``_bwd_kernel`` is allowed, from its shapes.  What it
    declares: dq^T of the whole sequence (seq x width float32: 0.5 MB at
    gpt2m_train's 1,024 x 128, 4 MB at 8k), delta (a head pads to 8
    sublanes), dk and dv between grid steps, the three staging buffers and
    the pipeline's two copies of every fetched block.  What the compiler
    keeps of the largest tile the body issues (a sub-tile where the sequence
    is one block, else a WHOLE cell below the diagonal, 4 MB of float32 at
    1024 x 1024): it held two tiles and three to five float32 operand
    blocks at every shape read (18.4 MB at seq 4,096 x 128 lanes, 27.8 MB at
    4,096 x 256; libtpu's report, PR 55); three and six are asked for."""
    f32 = 4
    declared = (
        seq * width * f32 + (seq // block_q) * 8 * block_q * f32 + 2 * n * block_k * width * f32
        + 2 * (block_q + 2 * block_k) * width * itemsize           # staging, two slots each
        + 2 * ((3 * block_q + 2 * block_k) * width * itemsize + 8 * block_q * f32)  # fetched
    )
    whole = seq - block_q >= block_k - 1  # ``_walk_cell``'s rule: a cell below the diagonal exists
    tile_q, tile_k = (block_q, block_k) if whole else _sub_tiles(block_q, block_k)
    kept = 3 * tile_q * tile_k * f32 + 6 * max(block_q, block_k) * width * f32
    return max(_VMEM_DEFAULT, declared + kept)


def _flash_bwd(q, k, v, first, heads, hd, out, lse, do, *, block_q, block_k):
    """dq, dk, dv side by side in ONE (batch, seq, 3 x heads x head_dim)
    array, as a fused projection holds q, k and v, from ONE kernel."""
    b, seq, _ = q.shape
    n = _heads_per_block(heads, hd)
    width, part = n * hd, heads // n  # a column block; column blocks of one of dq, dk, dv
    n_q = seq // block_q
    vmem = _bwd_vmem_bytes(seq, width, n, block_q, block_k, q.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=1.0 / (hd**0.5), seq=seq, hd=hd),
        grid=(b, part, seq // block_k, n_q),
        in_specs=[
            _col_spec(block_q, width, first[0], 3),
            _col_spec(block_k, width, first[1], 2),
            _col_spec(block_k, width, first[2], 2),
            _col_spec(block_q, width, 0, 3),
            _col_spec(block_q, width, 0, 3),
            _stat_spec(n, block_q, 3),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((b, seq, 3 * heads * hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((n_q, width, block_q), jnp.float32),  # dq^T, the whole sequence
            pltpu.VMEM((n_q, n, block_q), jnp.float32),      # delta, rows in the lanes
            pltpu.VMEM((n, block_k, width), jnp.float32),    # dk, dv between q blocks
            pltpu.VMEM((n, block_k, width), jnp.float32),
            pltpu.VMEM((2, block_q, width), q.dtype),        # dq, dk, dv on their way out
            pltpu.VMEM((2, block_k, width), q.dtype),
            pltpu.VMEM((2, block_k, width), q.dtype),
            pltpu.SemaphoreType.DMA((3, 2)),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        interpret=_interpret(),
        name="flash_bwd",
    )(q, k, v, do, out, lse)


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


def _blocks(seq, heads, hd, block_q, block_k, block_q_bwd, block_k_bwd):
    """The four grid blocks, clamped to the sequence; on a TPU everything
    must be whole tiles, or this raises."""
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
    bq, bk = _pick_blocks(seq, block_q, block_k)
    bqb, bkb = _pick_blocks(seq, block_q_bwd, block_k_bwd)
    width = _heads_per_block(heads, hd) * hd
    if not _interpret() and (bq % 128 or bk % 128 or bqb % 128 or bkb % 128 or width % 128):
        # never a silent change of implementation: ``auto`` callers are
        # routed by ``ops.attention.auto_impl`` before they get here
        raise ValueError(
            f"flash attention on TPU needs blocks that tile by 128 (Mosaic "
            f"lane constraint); seq={seq} picked fwd {bq}x{bk}, bwd {bqb}x{bkb}, "
            f"{heads} heads of {hd} a column block of {width} lanes "
            "— use impl='xla' for this shape"
        )
    n = _heads_per_block(heads, hd)
    if not _interpret() and _bwd_vmem_bytes(seq, width, n, bqb, bkb, 4) > _VMEM_MOST:
        # the one backward kernel holds dq^T of a head block's whole sequence
        raise ValueError(
            f"flash attention's backward needs {seq * width * 4 / 2**20:.0f} MB of fast memory "
            f"for dq at seq={seq} and a column block of {width} lanes, and a kernel may ask for "
            f"{_VMEM_MOST / 2**20:.0f}: shard the sequence (ops.ring_attention)"
        )
    return bq, bk, bqb, bkb


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _flash_core(operands, heads, hd, blocks):
    """``operands``: ``(qkv,)``, ONE (batch, seq, 3 x heads x hd) array
    that holds q, k and v side by side as a fused projection writes them, or
    ``(q, k, v)``, three (batch, seq, heads x hd) arrays.  Returns (batch,
    seq, heads x hd); the cotangents come back in the operands' own form."""
    return _flash_core_fwd(operands, heads, hd, blocks)[0]


def _first_blocks(operands, heads, hd):
    """The three arrays the kernels read and the column block at which the
    heads of q, k and v start in each."""
    if len(operands) == 3:
        return operands, (0, 0, 0)
    n = heads // _heads_per_block(heads, hd)  # column blocks of one of q, k, v
    return operands * 3, (0, n, 2 * n)


def _flash_core_fwd(operands, heads, hd, blocks):
    (q, k, v), first = _first_blocks(operands, heads, hd)
    out, lse = _flash_fwd(q, k, v, first, heads, hd, block_q=blocks[0], block_k=blocks[1])
    # Name the kernel's own residuals so a jax.checkpoint policy
    # (save_only_these_names, models/gpt.py remat_policy="attn"/"big") can
    # keep exactly these and dead-code the whole forward kernel out of the
    # rematerialized backward — the single biggest recompute in a
    # full-remat transformer block.
    from jax.ad_checkpoint import checkpoint_name

    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (operands, out, lse)


def _flash_core_bwd(heads, hd, blocks, res, do):
    operands, out, lse = res
    (q, k, v), first = _first_blocks(operands, heads, hd)
    dqkv = _flash_bwd(
        q, k, v, first, heads, hd, out, lse, do, block_q=blocks[2], block_k=blocks[3])
    return ((dqkv,) if len(operands) == 1 else tuple(jnp.split(dqkv, 3, axis=-1)),)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention_packed(
    qkv: jax.Array,
    heads: int,
    block_q: int | None = None,
    block_k: int | None = None,
    block_q_bwd: int | None = None,
    block_k_bwd: int | None = None,
) -> jax.Array:
    """Causal flash attention over a fused projection's output. qkv:
    (batch, seq, 3 x heads x head_dim), q, k and v side by side; returns
    (batch, seq, heads x head_dim), what the output projection reads.

    The kernels take every head as a column block of these arrays (module
    docstring): nothing is split, transposed or copied on either side, and
    the gradient comes back as ONE (batch, seq, 3 x heads x head_dim) array.
    O(seq) HBM; O(block) VMEM forward, plus dq of a head block's sequence
    in the backward (module docstring); differentiable (custom VJP with
    blockwise-recompute backward).  Raises on a TPU where the shapes do not
    tile, as ``flash_attention`` does."""
    b, s, width = qkv.shape
    hd = width // (3 * heads)
    if width != 3 * heads * hd:
        raise ValueError(f"qkv of {width} columns is not 3 x {heads} heads")
    blocks = _blocks(s, heads, hd, block_q, block_k, block_q_bwd, block_k_bwd)
    return _flash_core((qkv,), heads, hd, blocks)


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

    The thin head-major entry, for callers that hold head-major arrays
    already (rotary models, the ``shard_map``'d form): it lays q, k, v out
    as (batch, seq, heads x head_dim), the layout the kernels read and
    write (a head's 64 lanes alone are half a lane tile; module docstring),
    and the output back.  A caller with a fused projection uses
    ``flash_attention_packed`` and pays for no layout at all.

    O(seq) HBM; O(block) VMEM forward, plus dq of a head block's sequence
    in the backward (module docstring); differentiable (custom VJP with
    blockwise-recompute backward). Forward and backward grid blocks may
    differ (the backward kernel has another reuse pattern than the
    forward). On TPU the blocks must tile by 128 and a column block
    (``_heads_per_block`` heads) must be whole 128-lane tiles (Mosaic lane
    constraint), and the backward's dq must fit a core's fast memory
    (``_bwd_vmem_bytes``) — anything else raises; interpret mode (CPU CI)
    accepts any power-of-two-friendly blocking.
    """
    b, h, s, d = q.shape
    blocks = _blocks(s, h, d, block_q, block_k, block_q_bwd, block_k_bwd)
    lay = lambda t: t.transpose(0, 2, 1, 3).reshape(b, s, h * d)  # noqa: E731
    out = _flash_core((lay(q), lay(k), lay(v)), h, d, blocks)
    return out.reshape(b, s, h, d).transpose(0, 2, 1, 3)


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

    Head-major on purpose: under ``tp`` the fused projection's columns are
    split across chips as one run, so a chip's shard interleaves q, k and v
    and is no ``flash_attention_packed`` operand; the caller's transposes
    stay, and ``flash_attention`` lays each shard out for the kernels.
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
