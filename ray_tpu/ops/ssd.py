"""Mamba-2 (SSD, arXiv:2405.21060): the decode update and the chunk form.

The recurrence, a head ``h`` of ``H`` with ``P`` channels and a state of
``N`` columns, ``g(h)`` the head's group of ``G`` (heads in consecutive runs
of ``H / G``)::

    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g(h)]
    y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]

``A`` is one negative number a head, so a token's decay is ONE scalar a head:
that is what lets a run of tokens be written as matrix products (below).  A
state is ``(H, P, N)`` float32: ``N`` (256) along the lanes, ``P`` (128)
along the sublanes, whole ``(8, 128)`` tiles.  A pool of states is ``(slots,
H, P, N)``.

``ssd_decode`` is the decode step's update and read for a batch of rows,
each on the slot its table names: ONE Pallas kernel that walks the LIVE rows
only (scalar-prefetched, compacted: the pattern of
``ops.power_retention``'s), reads a block of ``heads_per_block`` heads' states
once, scales, adds the rank-one update, answers ``C`` against the updated
state and writes it back once, aliased onto its input.  A dead row's grid
steps name the block of the live step before them, so nothing is fetched or
written for it.  Everything the kernel is given lies along the lanes (``x
dt`` a row of ``P``, the decay, ``B`` and ``C`` rows of ``N``): a column a
channel would be padded to 128 lanes in HBM, so the one column the update
needs, ``x dt`` down the sublanes, is made in the kernel (a row laid on the
diagonal of a ``(P, P)`` tile and summed along the lanes), and ``y`` goes back
to a row the same way.  ``impl="xla"`` is the same function as gather,
update and scatter (the CPU path; three passes over the rows' states).

``ssd_chunk`` is a prefill chunk of ONE sequence in the matrix-product form,
sub-chunks of ``sub`` tokens (the architecture's ``mamba_chunk_size``): with
``a_t = dt_t A`` and ``cum`` its running sum inside a sub-chunk,

* inside a sub-chunk ``y_t += sum_{s <= t} (C_t . B_s) exp(cum_t - cum_s)
  dt_s x_s``: ``(C B^T . L) X``;
* a sub-chunk's own contribution to the state at its end ``sum_s exp(cum_end
  - cum_s) dt_s x_s (x) B_s``, the states at the sub-chunks' starts by the
  recurrence over SUB-CHUNKS (4 steps at 512 / 128) from the state the chunk
  was entered with;
* across sub-chunks ``y_t += exp(cum_t) S_start C_t``: ``C S``.

A token of the padded tail gets ``dt = 0``: decay 1 and no update, so the
state the chunk leaves is the one after its last valid token.  Every product
takes float32 inputs at ``HIGHEST``: they are 2.7 GFLOP a layer a 512-token
chunk at the published sizes, 3% of the layer's projections, and the state
(float32 in the pool) is never rounded on its way through them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
#: what one grid step of the decode kernel moves of the pool, each way
_BLOCK_BYTES = 1 << 20


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def heads_per_block(heads: int, p: int, n: int) -> int:
    """Heads of one row the decode kernel moves a grid step: the most that
    divide ``heads`` and keep the block within ``_BLOCK_BYTES`` (8 at 128 x
    256 float32; a head alone is too short a copy to keep HBM busy, a whole
    row's 32 would need 16 MB of VMEM for its two double buffers), and a
    multiple of 8 (the rows the kernel is given are ``(heads, lanes)``
    tiles) unless it is all of them."""
    best = heads
    for hb in range(1, heads + 1):
        if heads % hb == 0 and hb % 8 == 0 and hb * p * n * 4 <= _BLOCK_BYTES:
            best = hb
    return best


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def _decode_core_xla(state, dtx, decay, bh, ch, slots, live):
    """state (NS, H, P, N); dtx (S, H, P); decay (S, H); bh, ch (S, H, N);
    slots (S,) int32; live (S,) bool.  Returns (state, y (S, H, P)); a dead
    row writes nothing and reads 0."""
    old = state[slots].astype(jnp.float32)
    new = decay[:, :, None, None] * old + dtx[..., None] * bh[:, :, None, :]
    y = (new * ch[:, :, None, :]).sum(axis=-1)
    where = jnp.where(live, slots, state.shape[0])  # out of range: dropped
    state = state.at[where].set(new.astype(state.dtype), mode="drop")
    return state, jnp.where(live[:, None, None], y, 0.0)


def _decode_kernel(rows_ref, slots_ref, n_ref, s_ref, x_ref, g_ref, b_ref, c_ref,
                   o_ref, y_ref, *, hb: int):
    """One (live row, block of ``hb`` heads).  ``s_ref`` / ``o_ref`` (1, hb,
    P, N) are the same states in HBM; ``x_ref`` (1, hb, P) is ``x dt``;
    ``g_ref``, ``b_ref``, ``c_ref`` (1, hb, N): the decay (one number a head,
    along the lanes), ``B`` and ``C`` of each head's group; ``y_ref`` (1, hb,
    P)."""
    from jax.experimental import pallas as pl

    r, j = pl.program_id(0), pl.program_id(1)
    n_live = n_ref[0]
    p = s_ref.shape[2]

    @pl.when(r < n_live)
    def _():
        diag = (jax.lax.broadcasted_iota(jnp.int32, (p, p), 0)
                == jax.lax.broadcasted_iota(jnp.int32, (p, p), 1))
        for h in range(hb):
            # the row x dt (1, P) as a column (P, 1): on the diagonal, summed
            # along the lanes
            col = jnp.sum(jnp.where(diag, x_ref[0, h:h + 1, :], 0.0), axis=1, keepdims=True)
            new = g_ref[0, h:h + 1, :] * s_ref[0, h].astype(jnp.float32) \
                + col * b_ref[0, h:h + 1, :]
            o_ref[0, h] = new.astype(o_ref.dtype)
            y = jnp.sum(new * c_ref[0, h:h + 1, :], axis=1, keepdims=True)   # (P, 1)
            y_ref[0, h:h + 1, :] = jnp.sum(jnp.where(diag, y, 0.0), axis=0, keepdims=True)

    # no live row at all: every step names ONE block, which goes back as it
    # came (the output buffer is written out whatever the body did)
    @pl.when((n_live == 0) & (r == 0) & (j == 0))
    def _():
        o_ref[...] = s_ref[...]


def _decode_core_pallas(state, dtx, decay, bh, ch, slots, live, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, P = dtx.shape
    N = state.shape[3]
    hb = heads_per_block(H, P, N)
    J = H // hb
    # live rows first, in slot order; the rest repeat the last live row, and
    # their steps name the block that step left: nothing moves for them
    n_live = jnp.sum(live.astype(jnp.int32))
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    rows = order[jnp.minimum(jnp.arange(S), jnp.maximum(n_live - 1, 0))]

    def by_row(r, j, rows_ref, slots_ref, n_ref):
        return (rows_ref[r], jnp.where(r < n_ref[0], j, J - 1), 0)

    def by_slot(r, j, rows_ref, slots_ref, n_ref):
        return (slots_ref[r], jnp.where(r < n_ref[0], j, J - 1), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, J),
        in_specs=[
            pl.BlockSpec((1, hb, P, N), by_slot),
            pl.BlockSpec((1, hb, P), by_row),
            pl.BlockSpec((1, hb, N), by_row),
            pl.BlockSpec((1, hb, N), by_row),
            pl.BlockSpec((1, hb, N), by_row),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, P, N), by_slot),
            pl.BlockSpec((1, hb, P), by_row),
        ],
    )
    block = hb * P * N * state.dtype.itemsize
    state, y = pl.pallas_call(
        functools.partial(_decode_kernel, hb=hb),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct((S, H, P), jnp.float32),
        ],
        # operand 3 (after the three prefetched scalars) is the state
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the state block in and out, each double-buffered, and the rest
            vmem_limit_bytes=int(4 * block + (8 << 20)),
        ),
        interpret=interpret,
        name="ssd_decode",
    )(rows, slots[rows].astype(jnp.int32), n_live[None], state, dtx,
      jnp.broadcast_to(decay[:, :, None], bh.shape), bh, ch)
    return state, jnp.where(live[:, None, None], y, 0.0)


def ssd_decode(state, x, dt, a, b, c, d_skip, slots, live, *, impl: str = "auto"):
    """One decode step of a batch of rows against a pool of states.

    state (NS, H, P, N) — donated by the caller, updated in place; x (S, H,
    P); dt (S, H) float32, after the softplus; a (H,) negative; b, c (S, G,
    N); d_skip (H,); slots (S,) int32, the state each row owns; live (S,)
    bool.  Returns (state, y (S, H, P) float32); a dead row's state is
    untouched and its ``y`` is ``D x``."""
    if impl not in ("auto", "xla", "pallas"):
        raise ValueError(f"unknown ssd impl {impl!r}; expected 'auto', 'xla' or 'pallas'")
    f32 = jnp.float32
    H, G = x.shape[1], b.shape[1]
    x, dt = x.astype(f32), dt.astype(f32)
    args = (state, x * dt[:, :, None], jnp.exp(dt * a.astype(f32)),
            jnp.repeat(b.astype(f32), H // G, axis=1),
            jnp.repeat(c.astype(f32), H // G, axis=1), slots.astype(jnp.int32), live)
    if impl == "xla" or (impl == "auto" and not _on_tpu()):
        state, y = _decode_core_xla(*args)
    else:
        state, y = _decode_core_pallas(*args, interpret=not _on_tpu())
    return state, y + d_skip.astype(f32)[:, None] * x


# ---------------------------------------------------------------------------
# prefill chunk
# ---------------------------------------------------------------------------


def ssd_chunk(s0, x, dt, a, b, c, d_skip, valid, *, sub: int = 128):
    """A chunk of ONE sequence.  s0 (H, P, N) float32: the state the chunk is
    entered with (zeros for a sequence's first); x (C, H, P); dt (C, H)
    float32, after the softplus; a (H,) negative; b, c (C, G, N); d_skip
    (H,); valid (C,) bool, a prefix.  ``sub``: tokens a sub-chunk; ``C`` is a
    whole number of them (a shorter chunk is one sub-chunk).  Returns (y (C,
    H, P) float32, the state after the chunk's last valid token)."""
    f32 = jnp.float32
    C, H, P = x.shape
    G, N = b.shape[1], b.shape[2]
    K = H // G
    q = min(sub, C)
    if C % q:
        raise ValueError(f"a chunk of {C} tokens is no whole number of sub-chunks of {q}")
    nc = C // q
    x, a = x.astype(f32), a.astype(f32)
    dt = jnp.where(valid[:, None], dt.astype(f32), 0.0)
    cum = jnp.cumsum((dt * a).reshape(nc, q, G, K), axis=1)          # (nc, q, G, K), <= 0
    xd = (x * dt[:, :, None]).reshape(nc, q, G, K, P)
    bq, cq = b.astype(f32).reshape(nc, q, G, N), c.astype(f32).reshape(nc, q, G, N)
    # inside a sub-chunk: (C B^T . L) X, L_ts = exp(cum_t - cum_s) for s <= t
    cb = jnp.einsum("ctgn,csgn->cgts", cq, bq, precision=_HI)         # (nc, G, t, s)
    causal = (jnp.arange(q)[:, None] >= jnp.arange(q)[None, :])[None, :, :, None, None]
    seg = cum[:, :, None] - cum[:, None, :]                           # (nc, t, s, G, K)
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    w = cb.transpose(0, 2, 3, 1)[..., None] * decay                   # (nc, t, s, G, K)
    y = jnp.einsum("ctsgk,csgkp->ctgkp", w, xd, precision=_HI)
    # each sub-chunk's own part of the state at its end, and what is left
    # there of the state at its start
    tail = jnp.exp(cum[:, -1:] - cum)                                 # (nc, q, G, K)
    own = jnp.einsum("csgkp,csgn->cgkpn", xd * tail[..., None], bq, precision=_HI)
    keep = jnp.exp(cum[:, -1])                                        # (nc, G, K)

    def over_subchunks(s, inputs):
        own_c, keep_c = inputs
        return keep_c[:, :, None, None] * s + own_c, s                # emits the START state

    s1, starts = jax.lax.scan(over_subchunks, s0.astype(f32).reshape(G, K, P, N), (own, keep))
    # across sub-chunks: exp(cum_t) C_t S_start
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "ctgn,cgkpn->ctgkp", cq, starts, precision=_HI)
    y = y.reshape(C, H, P) + d_skip.astype(f32)[:, None] * x
    return y, s1.reshape(H, P, N)
