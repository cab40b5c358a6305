"""Power retention (degree 2): a recurrent state in place of keys and values.

Per key-value head, with its group of query heads, gate ``g_t`` and key
width ``d``::

    attention form   w_ts = exp(a_t - a_s) (q_t . k_s)^2 / d     (s <= t)
                     y_t  = sum_s w_ts v_s / (sum_s w_ts + eps)
    recurrent form   S_t  = g_t S_{t-1} + phi(k_t) [v_t, 1]^T
                     y_t  = phi(q_t)^T S_t[:, :d] / (phi(q_t)^T S_t[:, d] + eps)

``a_t`` is the running sum of ``log g``; ``phi(x)`` is the symmetric square
of ``x / d^(1/4)``: the ``d (d + 1) / 2`` products ``x_i x_j`` (``i <= j``),
those off the diagonal scaled by sqrt(2), so ``phi(q) . phi(k) = (q . k)^2 /
d``.  The two forms are the same function; a sequence's whole past is the
one ``d (d + 1) / 2 x (d + 1)`` state, whatever its length.

Layout of a state on the device: ``(value rows, features)`` = ``(d + 1, d
(d + 1) / 2)`` padded with ZEROS to ``(VD, F)``, multiples of the float32
tile (8, 128); at ``d = 128`` that is (136, 8320) for (129, 8256).  The
feature axis is the long one, so it is the lane axis; the padding's
features have scale 0 and its value rows a zero ``[v, 1]`` entry, so a
padded cell is 0 after every update.  A pool of states is ``(slots, kv
heads, VD, F)`` float32.

``retention_decode`` is the decode step's update and read for a batch of
rows, each on the slot its table names: a Pallas kernel that walks the LIVE
rows only (scalar-prefetched, compacted).  The pool stays in HBM, aliased
onto the kernel's output, and the kernel copies a (row, kv head)'s state
itself between it and two VMEM buffers: while one state is updated in place
in its buffer the next is read into the other, and only when that has
arrived is the updated one written back onto itself.  A state's read and
another's write are never in flight together: a v5e moves both at the speed
of the write alone (657 GB/s) when they are, and the read at 756 GB/s when
it goes alone.  ``k``, ``q`` and ``v`` enter as XLA made them, a row's heads
together and lane dense; at a row's first head its ``k`` and ``q`` rows are
set head by head into a scratch (Mosaic loads no row at a sublane it only
learns when the kernel runs, and a head is then a LEADING index), and ``v``
is turned to columns in registers.  In the buffer the value rows go in
static blocks of four or five groups of eight (17 groups: 4 + 4 + 4 + 5; the
shapes decide, no knob): a block walks the feature vregs once, ``k`` and the
group's ``q`` vregs of a feature vreg loaded once a BLOCK, each state vreg
loaded, scaled, given its rank-one update and stored once (``g s + v k``,
float32, term for term), the partial sums of (group, query head) whole vregs
on the VPU until one cross-lane sum ends each; ``y`` leaves as ``(G, VD)``
rows.  A dead row's grid steps move nothing.  ``impl="xla"`` is the same function as gather,
einsum and scatter (the CPU path).  ``retention_chunk`` is a prefill chunk
of ONE sequence: the attention form inside the chunk, the state across
chunks, XLA einsums.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST


def state_dims(head_dim: int) -> tuple:
    """(VD, F): a state's padded (value rows, features)."""
    feats = head_dim * (head_dim + 1) // 2
    return -(-(head_dim + 1) // 8) * 8, -(-feats // 128) * 128


@functools.lru_cache(maxsize=None)
def _phi_tables(head_dim: int):
    """One-hot selectors ``(d, F)`` of a feature's two factors and the
    feature's scale ``(F,)``: diagonal first, then the rows of the strict
    upper triangle; the padding selects nothing at scale 0."""
    _, F = state_dims(head_dim)
    ia, ib = np.triu_indices(head_dim, 1)
    ia = np.concatenate([np.arange(head_dim), ia])
    ib = np.concatenate([np.arange(head_dim), ib])
    n = len(ia)
    pa = np.zeros((head_dim, F), np.float32)
    pb = np.zeros((head_dim, F), np.float32)
    pa[ia, np.arange(n)] = 1.0
    pb[ib, np.arange(n)] = 1.0
    scale = np.zeros(F, np.float32)
    scale[:head_dim] = head_dim**-0.5
    scale[head_dim:n] = (2.0 / head_dim) ** 0.5
    return pa, pb, scale


def phi(x: jax.Array) -> jax.Array:
    """Symmetric square of ``x / d^(1/4)`` along the last axis, float32,
    padded to ``F``.  The two factors of each feature are SELECTED by a
    one-hot product (exact: one term a sum), never gathered: a gather along
    the lane axis is slow on a TPU."""
    pa, pb, scale = _phi_tables(x.shape[-1])
    a = jnp.dot(x, pa.astype(x.dtype), precision=_HI, preferred_element_type=jnp.float32)
    b = jnp.dot(x, pb.astype(x.dtype), precision=_HI, preferred_element_type=jnp.float32)
    return a * b * scale


def _v_ext(v: jax.Array, vd: int) -> jax.Array:
    """``[v, 1, 0...]`` along the last axis, float32, ``vd`` long."""
    one = jnp.ones(v.shape[:-1] + (1,), jnp.float32)
    pad = jnp.zeros(v.shape[:-1] + (vd - v.shape[-1] - 1,), jnp.float32)
    return jnp.concatenate([v.astype(jnp.float32), one, pad], axis=-1)


def _normalise(y: jax.Array, d: int, eps: float) -> jax.Array:
    """(..., VD) numerator rows and the normaliser row -> (..., d)."""
    return y[..., :d] / (y[..., d:d + 1] + eps)


# ---------------------------------------------------------------------------
# decode: XLA form
# ---------------------------------------------------------------------------


def _decode_core_xla(state, phi_q, phi_k, v_ext, g, slots, live):
    """state (NS, H, VD, F); phi_q (S, H, G, F); phi_k (S, H, F); v_ext (S,
    H, VD); g (S, H); slots (S,) int32; live (S,) bool.  Returns (state,
    y (S, H, G, VD)); a dead row writes nothing and reads 0."""
    old = state[slots].astype(jnp.float32)
    new = g[:, :, None, None] * old + v_ext[..., None] * phi_k[:, :, None, :]
    y = jnp.einsum("shgf,shdf->shgd", phi_q, new, precision=_HI)
    where = jnp.where(live, slots, state.shape[0])  # out of range: dropped
    state = state.at[where].set(new.astype(state.dtype), mode="drop")
    return state, jnp.where(live[:, None, None, None], y, 0.0)


# ---------------------------------------------------------------------------
# decode: Pallas kernel
# ---------------------------------------------------------------------------


def _row_blocks(n: int, most: int = 5) -> list:
    """``n`` groups of eight value rows as static (first, count) blocks of at
    most ``most``, as even as they go: 17 -> 4 + 4 + 4 + 5, 3 -> 3."""
    blocks = -(-n // most)
    base, rem = divmod(n, blocks)
    sizes = [base] * (blocks - rem) + [base + 1] * rem
    return [(sum(sizes[:i]), size) for i, size in enumerate(sizes)]


def _update_and_read(s_ref, kq, v_ref, gate, y_ref, h, *, groups: int, vd: int):
    """``s_ref`` (VD, F), a state in VMEM, becomes ``gate s + v k^T`` IN
    PLACE and ``y_ref`` (1, 1, G, VD) its product with the group's query
    features.  ``kq`` (8, F) holds the head's ``k`` row and, under it, its G
    ``q`` rows; ``v_ref`` (1, H, VD) the row's heads, of which ``h`` is this
    one.  A block of four or five groups of eight value rows walks the
    feature vregs once: ``k`` and the G ``q`` vregs of a feature vreg are
    loaded (broadcast over sublanes) once a BLOCK, each state vreg is loaded
    and stored once, and a (group, head)'s partial sums stay one whole (8,
    128) vreg added on the VPU until ONE cross-lane sum ends them."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
    chunks = [(c, min(128, vd - c)) for c in range(0, vd, 128)]  # lane vregs of a (., VD) row
    # lane 8 i + j of a row's lane vreg is value row 8 i + j, sublane j of group i
    heads = jax.lax.broadcasted_iota(jnp.int32, (v_ref.shape[1], 1), 0) == h
    v_rows = [jnp.broadcast_to(jnp.max(  # the head's row picked, never summed: exact
        jnp.where(heads, v_ref[0, :, c:c + w], -jnp.inf), axis=0, keepdims=True), (8, w))
        for c, w in chunks]
    y = [[jnp.zeros((8, 128), f32) for _ in chunks] for _ in range(groups)]
    for first, count in _row_blocks(vd // 8):
        rows = [slice((first + i) * 8, (first + i + 1) * 8) for i in range(count)]
        diagonal = [lane == at.start % 128 + sub for at in rows]
        # v as COLUMNS: the one lane of the row a sublane wants, picked (never summed: exact)
        v = [jnp.broadcast_to(jnp.max(
            jnp.where(diagonal[i][:, :chunks[at.start // 128][1]], v_rows[at.start // 128], -jnp.inf),
            axis=-1, keepdims=True), (8, 128)) for i, at in enumerate(rows)]

        def feature_vreg(c, acc, rows=rows, v=v):
            lanes = pl.ds(pl.multiple_of(c * 128, 128), 128)
            k = jnp.broadcast_to(kq[0:1, lanes], (8, 128))
            q = [jnp.broadcast_to(kq[1 + j:2 + j, lanes], (8, 128)) for j in range(groups)]
            out = []
            for i, at in enumerate(rows):
                new = gate * s_ref[at, lanes].astype(f32) + v[i] * k
                s_ref[at, lanes] = new.astype(s_ref.dtype)
                out.append(tuple(acc[i][j] + new * q[j] for j in range(groups)))
            return tuple(out)

        zero = tuple(tuple(jnp.zeros((8, 128), f32) for _ in range(groups)) for _ in rows)
        acc = jax.lax.fori_loop(0, s_ref.shape[-1] // 128, feature_vreg, zero)
        for i, at in enumerate(rows):
            for j in range(groups):
                column = jnp.sum(acc[i][j], axis=-1, keepdims=True)
                y[j][at.start // 128] += jnp.where(diagonal[i], column, 0.0)
    for j in range(groups):
        for (c, w), part in zip(chunks, y[j]):
            y_ref[0, 0, j:j + 1, c:c + w] = jnp.sum(part, axis=0, keepdims=True)[:, :w]


def _decode_kernel(rows_ref, slots_ref, n_ref, s_hbm, k_ref, q_ref, v_ref, g_ref,
                   o_hbm, y_ref, buf, kq, sem, *, groups: int, vd: int, heads: int):
    """Grid step (r, h) is block ``t = r H + h`` of the walk over the live
    rows' states.  ``s_hbm``/``o_hbm`` (NS, H, VD, F) are the same pool in
    HBM; ``buf`` (2, VD, F) holds block ``t`` in ``buf[t % 2]`` when the
    step starts.  The step asks for block ``t + 1``, updates its own in
    place while that arrives, and only then writes its own back: a state's
    read and another's write are never in flight together (a v5e moves
    both at the speed of the write alone when they are).  ``k_ref`` (1, H,
    F), ``q_ref`` (1, H G, F), ``v_ref`` (1, H, VD): the row's heads, lane
    dense as XLA made them; ``kq`` (H, 8, F): the same ``k`` and ``q`` rows
    head by head, set at the row's first head; ``g_ref`` (S, H) in SMEM;
    ``y_ref`` (1, 1, G, VD)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, h = pl.program_id(0), pl.program_id(1)
    t = r * heads + h
    blocks = n_ref[0] * heads
    b = t % 2

    def fetch(t, b):
        return pltpu.make_async_copy(
            s_hbm.at[slots_ref[t // heads], t % heads], buf.at[b], sem.at[b])

    @pl.when(t < blocks)  # a dead row's steps move nothing
    def _():
        @pl.when(t == 0)
        def _():
            first = fetch(0, 0)
            first.start()
            first.wait()

        @pl.when(t + 1 < blocks)
        def _():
            fetch(t + 1, 1 - b).start()

        @pl.when(h == 0)  # the row's k and q rows, head by head: kq[h] = [k_h, q_hG .. q_hG+G-1]
        def _():
            for head in range(heads):
                kq[head, 0:1, :] = k_ref[0, head:head + 1, :]
                kq[head, 1:1 + groups, :] = q_ref[0, head * groups:(head + 1) * groups, :]

        gate = jnp.full((8, 128), g_ref[rows_ref[r], h], jnp.float32)
        _update_and_read(buf.at[b], kq.at[h], v_ref, gate, y_ref, h, groups=groups, vd=vd)

        @pl.when(t + 1 < blocks)
        def _():
            fetch(t + 1, 1 - b).wait()

        back = pltpu.make_async_copy(buf.at[b], o_hbm.at[slots_ref[r], h], sem.at[2])
        back.start()
        back.wait()


def _decode_core_pallas(state, phi_q, phi_k, v_ext, g, slots, live, interpret):
    """As ``_decode_core_xla``, but ``phi_q`` is (S, H G, F): the query heads
    as the model has them, a key-value head's group side by side."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, F = phi_k.shape
    G = phi_q.shape[1] // H
    vd = state.shape[2]
    # live rows first, in slot order; the rest repeat the last live row, and
    # their steps name the blocks that step left: nothing moves for them
    n_live = jnp.sum(live.astype(jnp.int32))
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    rows = order[jnp.minimum(jnp.arange(S), jnp.maximum(n_live - 1, 0))]

    def heads_of_row(r, h, rows_ref, slots_ref, n_ref):
        return (rows_ref[r], 0, 0)

    def by_row(r, h, rows_ref, slots_ref, n_ref):
        return (rows_ref[r], jnp.where(r < n_ref[0], h, H - 1), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, H),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, H, F), heads_of_row),
            pl.BlockSpec((1, H * G, F), heads_of_row),
            pl.BlockSpec((1, H, vd), heads_of_row),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, 1, G, vd), by_row),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, vd, F), state.dtype),
            pltpu.VMEM((H, -(-(1 + G) // 8) * 8, F), jnp.float32),
            pltpu.SemaphoreType.DMA((3,)),
        ],
    )
    block = vd * F * state.dtype.itemsize
    state, y = pl.pallas_call(
        functools.partial(_decode_kernel, groups=G, vd=vd, heads=H),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct((S, H, G, vd), jnp.float32),
        ],
        # operand 3 (after the three prefetched scalars) is the state
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # two states; a row's k, q, v double-buffered; k and q again head by head
            vmem_limit_bytes=int(2 * block + (2 * (H * G + 2 * H) + 8 * H) * F * 4 + (4 << 20)),
        ),
        interpret=interpret,
        name="retention_decode",
    )(rows, slots[rows].astype(jnp.int32), n_live[None], state, phi_k, phi_q, v_ext, g)
    return state, jnp.where(live[:, None, None, None], y, 0.0)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def retention_decode(state, q, k, v, log_g, slots, live, *, eps: float,
                     impl: str = "auto"):
    """One decode step of a batch of rows against a pool of states.

    state (NS, H, VD, F) — donated by the caller, updated in place; q (S,
    Hq, d); k, v (S, H, d); log_g (S, H) float32; slots (S,) int32, the
    state each row owns; live (S,) bool.  Returns (state, out (S, Hq, d)
    float32); a dead row's state is untouched and its ``out`` is 0."""
    if impl not in ("auto", "xla", "pallas"):
        raise ValueError(f"unknown retention impl {impl!r}; expected 'auto', 'xla' or 'pallas'")
    S, Hq, d = q.shape
    H = k.shape[1]
    xla = impl == "xla" or (impl == "auto" and not _on_tpu())
    phi_q = phi(q)  # the kernel takes the query heads as they stand: (S, Hq, F)
    if xla:
        phi_q = phi_q.reshape(S, H, Hq // H, -1)
    phi_k = phi(k)
    g = jnp.exp(log_g.astype(jnp.float32))
    args = (state, phi_q, phi_k, _v_ext(v, state.shape[2]), g, slots.astype(jnp.int32), live)
    if xla:
        state, y = _decode_core_xla(*args)
    else:
        state, y = _decode_core_pallas(*args, interpret=not _on_tpu())
    return state, _normalise(y, d, eps).reshape(S, Hq, d)


# ---------------------------------------------------------------------------
# prefill chunk
# ---------------------------------------------------------------------------


def retention_chunk(s0, q, k, v, log_g, valid, *, eps: float):
    """A chunk of ONE sequence.  s0 (H, VD, F) float32: the state before
    the chunk (zeros for a sequence's first); q (C, Hq, d); k, v (C, H, d);
    log_g (C, H) float32; valid (C,) bool, a prefix.  Returns (out (C, Hq,
    d) float32, the state after the chunk's last valid token): the
    attention form among the chunk's tokens, ``s0`` for what came before."""
    C, Hq, d = q.shape
    H = k.shape[1]
    G = Hq // H
    vd = s0.shape[1]
    f32 = jnp.float32
    log_g = jnp.where(valid[:, None], log_g.astype(f32), 0.0)
    a = jnp.cumsum(log_g, axis=0)                                  # (C, H)
    qg = q.astype(f32).reshape(C, H, G, d)
    kf, ve = k.astype(f32), _v_ext(v, vd)
    # inside the chunk: w_ts = exp(a_t - a_s) (q_t . k_s)^2 / d, s <= t
    qk = jnp.einsum("thgd,shd->hgts", qg, kf, precision=_HI)
    decay = jnp.exp(a.T[:, :, None] - a.T[:, None, :])             # (H, t, s)
    causal = (jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]) & valid[None, :]
    w = jnp.where(causal[None, None], decay[:, None] * qk * qk / d, 0.0)
    y = jnp.einsum("hgts,shv->thgv", w, ve, precision=_HI)          # (C, H, G, VD)
    # before the chunk: exp(a_t) phi(q_t)^T S0
    phi_q = phi(q).reshape(C, H, G, -1)
    y = y + jnp.exp(a)[:, :, None, None] * jnp.einsum(
        "thgf,hvf->thgv", phi_q, s0, precision=_HI)
    # after it: S = exp(a_C) S0 + sum_s exp(a_C - a_s) phi(k_s) [v_s, 1]^T
    tail = jnp.where(valid[:, None], jnp.exp(a[-1][None, :] - a), 0.0)  # (C, H)
    s1 = jnp.exp(a[-1])[:, None, None] * s0 + jnp.einsum(
        "shv,shf->hvf", ve * tail[:, :, None], phi(k), precision=_HI)
    return _normalise(y, d, eps).reshape(C, Hq, d), s1
