"""Attention kernels: Pallas flash (interpret mode on CPU) and ring
attention over the sp mesh axis must agree with the XLA reference path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import causal_attention, _xla_attention
from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops.flash_attention import flash_attention


def _qkv(b=2, h=4, s=256, d=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return [jax.random.normal(k, (b, h, s, d), dtype) for k in ks]


def _f32_reference(q, k, v, w):
    """Dense causal attention and its three gradients in float32, whatever
    the inputs' dtype."""
    f32 = [t.astype(jnp.float32) for t in (q, k, v)]
    loss = lambda q, k, v: (_xla_attention(q, k, v) * w).sum()  # noqa: E731
    return _xla_attention(*f32), jax.grad(loss, argnums=(0, 1, 2))(*f32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("seq,block_q,block_k", [
    (1024, 1024, 1024),  # gpt2m_train's case: ONE grid cell a head, the diagonal one, 4 x 4 sub-tiles
    (512, 512, 512),     # the same with 2 x 2 sub-tiles
    (1024, 512, 512),    # 2 blocks: one grid cell below the diagonal, run as ONE unmasked tile
    (2048, 512, 512),    # 4 blocks: six cells below the diagonal, six dead
    (1024, 256, 512),    # unequal blocks of whole sub-tiles: the diagonal crosses cells at two offsets
    (1024, 512, 256),    # ... and with the kv block the smaller one (negative offsets)
    (256, 64, 128),      # blocks smaller than one sub-tile, unequal
    (128, 64, 64),       # blocks smaller than one sub-tile, a 2 x 2 grid
    (192, 128, 128),     # seq 192 isn't divisible by 128: _pick_blocks must shrink to 64
])
def test_flash_matches_dense(seq, block_q, block_k, dtype):
    """Forward AND the three gradients of the sub-tiled kernels against
    dense attention: float32 at the kernels' own tolerances, bf16 against
    the float32 dense reference of the same inputs."""
    q, k, v = _qkv(b=1, h=2, s=seq, dtype=dtype)
    w = jnp.cos(jnp.arange(64, dtype=jnp.float32))
    ref, g_ref = _f32_reference(q, k, v, w)

    def loss(q, k, v):
        return (flash_attention(q, k, v, block_q, block_k).astype(jnp.float32) * w).sum()

    out = flash_attention(q, k, v, block_q=block_q, block_k=block_k)
    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert out.dtype == dtype and all(g.dtype == dtype for g in grads)
    if dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
        for g, r in zip(grads, g_ref):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=5e-5)
    else:  # one bf16 rounding of the result (and of ``out`` before delta)
        np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref), atol=2e-2, rtol=1e-2)
        for g, r in zip(grads, g_ref):
            np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(r), atol=4e-2, rtol=2e-2)


def _brute_tiles(block_q, block_k, sub_q, sub_k, off):
    """(i, j, crossed) of the sub-tiles with a live score, from the mask
    itself; ``off`` None is a cell wholly below the diagonal."""
    rows = (block_k if off is None else off) + np.arange(block_q)[:, None]
    live = np.arange(block_k)[None, :] <= rows
    tiles = live.reshape(block_q // sub_q, sub_q, block_k // sub_k, sub_k).transpose(0, 2, 1, 3)
    return [(i, j, not tiles[i, j].all())
            for i in range(tiles.shape[0]) for j in range(tiles.shape[1]) if tiles[i, j].any()]


@pytest.mark.parametrize("block_q,block_k,sub_q,sub_k,n_diag", [
    (1024, 1024, 256, 256, 10),  # gpt2m_train: 10 of 16
    (1024, 1024, 256, 128, 20),  # 20 of 32
    (1024, 1024, 128, 128, 36),  # 36 of 64
    (512, 256, 128, 128, None),  # unequal blocks: two crossed offsets, 0 and -256
    (64, 128, 64, 128, None),    # a block smaller than a sub-tile is ONE sub-tile
])
def test_flash_issues_only_live_sub_tiles(block_q, block_k, sub_q, sub_k, n_diag):
    """The skipping is seen, not only timed: the sub-tile products a kernel
    body issues (``_live_tiles``, which all three kernels build their loops
    from) are exactly the sub-tiles that hold a live score, for a cell on
    the diagonal and for one below it; and ``_diag_offsets`` names exactly
    the grid cells the diagonal crosses."""
    offsets = fa._diag_offsets(block_q, block_k)
    seq = 4 * max(block_q, block_k)
    crossed, whole = set(), set()
    for q0 in range(0, seq, block_q):
        for k0 in range(0, seq, block_k):
            live = np.arange(k0, k0 + block_k)[None, :] <= np.arange(q0, q0 + block_q)[:, None]
            if live.any():
                (whole if live.all() else crossed).add(q0 - k0)
    assert crossed == set(offsets)
    assert all(off >= block_k - 1 for off in whole)  # how _walk_cell tells them
    for off in offsets + [None]:
        tiles = fa._live_tiles(block_q, block_k, sub_q, sub_k, off)
        assert tiles == _brute_tiles(block_q, block_k, sub_q, sub_k, off)
    total = (block_q // sub_q) * (block_k // sub_k)
    assert len(fa._live_tiles(block_q, block_k, sub_q, sub_k, None)) == total
    if n_diag is not None:
        assert len(fa._live_tiles(block_q, block_k, sub_q, sub_k, 0)) == n_diag < total


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("seq", [1024, 2048])
def test_flash_kernel_bodies_hold_only_live_products(seq):
    """Count the matrix products in the three kernels' bodies as traced:
    2, 3 and 4 a live sub-tile of the diagonal case, and where the grid
    has cells below the diagonal 2, 3 and 4 more for the whole case, which
    runs as ONE tile; nothing for a dead sub-tile or a dead cell."""
    block = 1024
    sub_q, sub_k = fa._sub_tiles(block, block)
    live = len(fa._live_tiles(block, block, sub_q, sub_k, 0))
    if seq > block:  # a grid of ONE cell a head does not even trace the whole case
        live += len(fa._live_tiles(block, block, block, block, None))
    assert live == (10 if seq == block else 11)
    x = jax.ShapeDtypeStruct((1, 1, seq, 64), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: fa.flash_attention(q, k, v).sum().astype(
        jnp.float32), argnums=(0, 1, 2)))(x, x, x)
    dots = {
        eqn.params["name"]: sum(
            e.primitive.name == "dot_general" for e in _eqns(eqn.params["jaxpr"]))
        for eqn in _eqns(jaxpr.jaxpr) if eqn.primitive.name == "pallas_call"
    }
    assert dots == {"flash_fwd": 2 * live, "flash_bwd_dq": 3 * live, "flash_bwd_dkv": 4 * live}


def test_causal_attention_auto_dispatch_small_seq():
    # tiny seq takes the XLA path; result identical either way
    q, k, v = _qkv(s=64)
    np.testing.assert_allclose(
        np.asarray(causal_attention(q, k, v, impl="auto")),
        np.asarray(_xla_attention(q, k, v)),
        atol=1e-6,
    )


def test_flash_sharded_matches_dense():
    """sp=1 multi-device mesh (dp=2, tp=2): the shard_map'd Pallas kernel
    must agree with dense attention, forward and gradients."""
    from jax.sharding import Mesh

    from ray_tpu.ops.flash_attention import flash_attention_sharded, flash_shardable

    devs = np.array(jax.devices()[:4]).reshape(2, 1, 2, 1)
    mesh = Mesh(devs, ("dp", "fsdp", "tp", "sp"))
    q, k, v = _qkv(b=2, h=4, s=128, d=32)
    assert flash_shardable(2, 4, mesh)
    assert not flash_shardable(3, 4, mesh)
    ref = _xla_attention(q, k, v)
    w = jnp.cos(jnp.arange(32))
    with mesh:
        out = jax.jit(lambda q, k, v: flash_attention_sharded(q, k, v, mesh))(q, k, v)
        g_sh = jax.jit(
            jax.grad(
                lambda q, k, v: (flash_attention_sharded(q, k, v, mesh) * w).sum(),
                argnums=(0, 1, 2),
            )
        )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    g_ref = jax.grad(lambda q, k, v: (_xla_attention(q, k, v) * w).sum(), argnums=(0, 1, 2))(
        q, k, v
    )
    for a, b in zip(g_ref, g_sh):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=5e-5)


def test_ring_attention_matches_dense():
    """sp=2 ring attention over the virtual CPU mesh == dense causal."""
    from jax.sharding import Mesh, PartitionSpec as P

    from ray_tpu.ops.ring_attention import ring_attention_sharded

    devs = np.array(jax.devices()[:8]).reshape(2, 1, 2, 2)
    mesh = Mesh(devs, ("dp", "fsdp", "tp", "sp"))
    q, k, v = _qkv(b=2, h=4, s=256, d=32)
    ref = _xla_attention(q, k, v)
    with mesh:
        out = jax.jit(lambda q, k, v: ring_attention_sharded(q, k, v, mesh))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_attention_grads_match_dense():
    from jax.sharding import Mesh

    from ray_tpu.ops.ring_attention import ring_attention_sharded

    devs = np.array(jax.devices()[:4]).reshape(1, 1, 1, 4)
    mesh = Mesh(devs, ("dp", "fsdp", "tp", "sp"))
    q, k, v = _qkv(b=1, h=1, s=128, d=32)
    w = jnp.sin(jnp.arange(32))

    def ring_loss(q, k, v):
        return (ring_attention_sharded(q, k, v, mesh) * w).sum()

    def ref_loss(q, k, v):
        return (_xla_attention(q, k, v) * w).sum()

    with mesh:
        g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_ring):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=5e-5)


def test_gpt_forward_with_ring_attention_matches_single():
    """Full GPT fwd with sp=2 mesh (ring path) == sp=1 (flash/xla path)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.models.gpt import GPTConfig, gpt_forward, gpt_init

    cfg = GPTConfig(
        vocab_size=256, seq_len=128, d_model=64, n_layers=2, n_heads=2, dtype="float32"
    )
    params = gpt_init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 256, jnp.int32)

    ref = gpt_forward(cfg, params, tokens)  # no mesh: dense path

    devs = np.array(jax.devices()[:8]).reshape(2, 1, 2, 2)
    mesh = Mesh(devs, ("dp", "fsdp", "tp", "sp"))
    with mesh:
        out = jax.jit(lambda p, t: gpt_forward(cfg, p, t, mesh))(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3)
