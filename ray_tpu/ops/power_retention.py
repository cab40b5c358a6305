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
rows only (scalar-prefetched, compacted), reads each row's state once,
scales, adds the rank-one update, answers the group's query heads against
the updated state and writes it back once, aliased onto its input.  A dead
row's grid steps name the block of the live step before them, so nothing is
fetched or written for it.  ``impl="xla"`` is the same function as gather,
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


def _decode_kernel(rows_ref, slots_ref, n_ref, s_ref, k_ref, q_ref, v_ref, g_ref,
                   o_ref, y_ref, *, groups: int, vd: int):
    """One (live row, kv head): eight value rows at a time, the row block's
    features whole.  ``s_ref``/``o_ref`` (1, 1, VD, F) are the same state in
    HBM; ``k_ref`` (1, 1, 1, F); ``q_ref`` (1, 1, G, F); ``v_ref``/``g_ref``
    (1, 1, VD, 1) columns; ``y_ref`` (1, 1, VD, G)."""
    from jax.experimental import pallas as pl

    r, h = pl.program_id(0), pl.program_id(1)
    n_live = n_ref[0]

    @pl.when(r < n_live)
    def _():
        def eight(i, carry):
            rows = pl.ds(pl.multiple_of(i * 8, 8), 8)
            new = g_ref[0, 0, rows, :] * s_ref[0, 0, rows, :].astype(jnp.float32) \
                + v_ref[0, 0, rows, :] * k_ref[0, 0]
            o_ref[0, 0, rows, :] = new.astype(o_ref.dtype)
            for j in range(groups):
                y_ref[0, 0, rows, j:j + 1] = jnp.sum(
                    new * q_ref[0, 0, j:j + 1, :], axis=-1, keepdims=True)
            return carry

        jax.lax.fori_loop(0, vd // 8, eight, 0)

    # no live row at all: every step names ONE block, which goes back as it
    # came (the output buffer is written out whatever the body did)
    @pl.when((n_live == 0) & (r == 0) & (h == 0))
    def _():
        o_ref[...] = s_ref[...]


def _decode_core_pallas(state, phi_q, phi_k, v_ext, g, slots, live, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, G, F = phi_q.shape
    vd = state.shape[2]
    # live rows first, in slot order; the rest repeat the last live row, and
    # their steps name the block that step left: nothing moves for them
    n_live = jnp.sum(live.astype(jnp.int32))
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    rows = order[jnp.minimum(jnp.arange(S), jnp.maximum(n_live - 1, 0))]

    def by_row(r, h, rows_ref, slots_ref, n_ref):
        return (rows_ref[r], jnp.where(r < n_ref[0], h, H - 1), 0, 0)

    def by_slot(r, h, rows_ref, slots_ref, n_ref):
        return (slots_ref[r], jnp.where(r < n_ref[0], h, H - 1), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, H),
        in_specs=[
            pl.BlockSpec((1, 1, vd, F), by_slot),
            pl.BlockSpec((1, 1, 1, F), by_row),
            pl.BlockSpec((1, 1, G, F), by_row),
            pl.BlockSpec((1, 1, vd, 1), by_row),
            pl.BlockSpec((1, 1, vd, 1), by_row),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, vd, F), by_slot),
            pl.BlockSpec((1, 1, vd, G), by_row),
        ],
    )
    block = vd * F * state.dtype.itemsize
    state, y = pl.pallas_call(
        functools.partial(_decode_kernel, groups=G, vd=vd),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct((S, H, vd, G), jnp.float32),
        ],
        # operand 3 (after the three prefetched scalars) is the state
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the state block in and out, each double-buffered, and the rest
            vmem_limit_bytes=int(4 * block + 12 * F * 4 * 8 + (4 << 20)),
        ),
        interpret=interpret,
        name="retention_decode",
    )(rows, slots[rows].astype(jnp.int32), n_live[None],
      state, phi_k[:, :, None, :], phi_q, v_ext[..., None],
      jnp.broadcast_to(g[:, :, None, None], v_ext.shape + (1,)))
    y = jnp.swapaxes(y, 2, 3)  # (S, H, G, VD)
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
    vd = state.shape[2]
    phi_q = phi(q).reshape(S, H, Hq // H, -1)
    phi_k = phi(k)
    g = jnp.exp(log_g.astype(jnp.float32))
    args = (state, phi_q, phi_k, _v_ext(v, vd), g, slots.astype(jnp.int32), live)
    if impl == "xla" or (impl == "auto" and not _on_tpu()):
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
