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
Causally-dead (q, kv) cells are skipped with ``pl.when``.

TPU tiling notes: per-row stats (logsumexp, delta) live as ``(bh, 8, seq)``
— value broadcast over 8 sublanes so the (sublane, lane) block shape
``(8, block_q)`` satisfies Mosaic's (8, 128) fp32 tile constraint. Sequence
lengths must tile by 128 on the TPU path (the public entry raises
otherwise; ``ops.attention.auto_impl`` routes such shapes to XLA).

This is the hot op behind ``ray_tpu.ops.attention.causal_attention`` — the
reference has no attention kernel of its own (user torch code runs inside
``train_loop_per_worker``); SURVEY.md §5.7 makes long-context attention a
first-class mandate for the TPU build. On non-TPU backends the same kernels
run under ``interpret=True`` so CI (virtual CPU mesh) exercises identical
code paths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _causal_mask(q_start, k_start, block_q, block_k):
    rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return cols <= rows


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *, scale):
    """Grid (bh, qi, kj), kj minor/sequential. Scratch carries the online
    softmax state across kj steps of one q block."""
    qi, kj = pl.program_id(1), pl.program_id(2)
    block_q, d = q_ref.shape[1], q_ref.shape[2]
    block_k = k_ref.shape[1]
    q_start = qi * block_q
    k_start = kj * block_k
    j_last = (q_start + block_q - 1) // block_k  # last causally-live kv block

    @pl.when(k_start <= q_start + block_q - 1)  # skip causally-dead cells
    def _():
        @pl.when(kj == 0)
        def _():
            m_sc[:] = jnp.full_like(m_sc, NEG_INF)
            l_sc[:] = jnp.zeros_like(l_sc)
            acc_sc[:] = jnp.zeros_like(acc_sc)

        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (BQ, BK)
        s = jnp.where(_causal_mask(q_start, k_start, block_q, block_k), s, NEG_INF)
        m_prev = m_sc[0]
        l_prev = l_sc[0]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=1)
        acc_sc[:] = acc_sc[:] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_sc[:] = jnp.broadcast_to(m_new[None, :], m_sc.shape)
        l_sc[:] = jnp.broadcast_to(l_new[None, :], l_sc.shape)

        @pl.when(kj == j_last)
        def _():
            l = jnp.maximum(l_sc[0], 1e-30)
            o_ref[0] = (acc_sc[:] / l[:, None]).astype(o_ref.dtype)
            lse = m_sc[0] + jnp.log(l)
            lse_ref[0] = jnp.broadcast_to(lse[None, :], lse_ref.shape[1:])


def _flash_fwd(q, k, v, *, block_q, block_k):
    bh, seq, d = q.shape
    scale = 1.0 / (d**0.5)
    grid = (bh, seq // block_q, seq // block_k)
    out, lse8 = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
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
            pltpu.VMEM((8, block_q), jnp.float32),   # running max (broadcast)
            pltpu.VMEM((8, block_q), jnp.float32),   # running denom
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
        ],
        interpret=_interpret(),
        name="flash_fwd",
    )(q, k, v)
    return out, lse8[:, :1, :]  # (bh, 1, seq)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_sc, *, scale):
    qi, kj = pl.program_id(1), pl.program_id(2)
    block_q, d = q_ref.shape[1], q_ref.shape[2]
    block_k = k_ref.shape[1]
    q_start, k_start = qi * block_q, kj * block_k
    j_last = (q_start + block_q - 1) // block_k

    @pl.when(k_start <= q_start + block_q - 1)
    def _():
        @pl.when(kj == 0)
        def _():
            dq_sc[:] = jnp.zeros_like(dq_sc)

        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        p = jnp.where(
            _causal_mask(q_start, k_start, block_q, block_k),
            jnp.exp(s - lse[:, None]),
            0.0,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None]) * scale
        dq_sc[:] = dq_sc[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

        @pl.when(kj == j_last)
        def _():
            dq_ref[0] = dq_sc[:].astype(dq_ref.dtype)


def _dkv_kernel(
    k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_sc, dv_sc, *, scale
):
    """Grid (bh, kb, qi), qi minor/sequential; accumulates dk/dv for one kv
    block across its causally-live q blocks."""
    kb, qi = pl.program_id(1), pl.program_id(2)
    block_k, d = k_ref.shape[1], k_ref.shape[2]
    block_q = q_ref.shape[1]
    k_start, q_start = kb * block_k, qi * block_q
    i_first = k_start // block_q     # first q block the diagonal touches
    n_q = pl.num_programs(2)

    @pl.when(q_start + block_q - 1 >= k_start)
    def _():
        @pl.when(qi == i_first)
        def _():
            dk_sc[:] = jnp.zeros_like(dk_sc)
            dv_sc[:] = jnp.zeros_like(dv_sc)

        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (BQ, BK)
        p = jnp.where(
            _causal_mask(q_start, k_start, block_q, block_k),
            jnp.exp(s - lse[:, None]),
            0.0,
        )
        dv_sc[:] = dv_sc[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None]) * scale
        dk_sc[:] = dk_sc[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

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
        functools.partial(_dq_kernel, scale=scale),
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
        functools.partial(_dkv_kernel, scale=scale),
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


def _env_block(name: str, default: int) -> int:
    import os

    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        return int(raw)
    except ValueError:
        # a typo'd sweep var must fail loudly, or every sweep point silently
        # benchmarks the identical default configuration
        raise ValueError(f"{name}={raw!r} is not an integer block size") from None


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
    blockwise-recompute backward). Forward and backward block shapes tune
    independently (the dQ/dKV kernels have different reuse patterns than the
    forward); defaults are overridable via RAY_TPU_FLASH_{BQ,BK,BQB,BKB} for
    sweeps. On TPU the blocks must tile by 128 (Mosaic lane constraint) —
    anything else raises; interpret mode (CPU CI) accepts any
    power-of-two-friendly blocking.
    """
    b, h, s, d = q.shape
    # Default 1024×1024 measured fastest on v5e at (bh 256, s 1024, d 64):
    # fewer, fatter grid steps win — the kernel is latency-bound per step at
    # small head_dim, not VMEM-bound (sweep: 4.1 ms/layer at 256×512 →
    # 2.6 ms at 1024×1024; jax's own tuned kernel measures 2.2 at this
    # shape). _pick_blocks clamps to the actual sequence length.
    block_q = block_q if block_q is not None else _env_block("RAY_TPU_FLASH_BQ", 1024)
    block_k = block_k if block_k is not None else _env_block("RAY_TPU_FLASH_BK", 1024)
    block_q_bwd = (
        block_q_bwd if block_q_bwd is not None else _env_block("RAY_TPU_FLASH_BQB", block_q)
    )
    block_k_bwd = (
        block_k_bwd if block_k_bwd is not None else _env_block("RAY_TPU_FLASH_BKB", block_k)
    )
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
