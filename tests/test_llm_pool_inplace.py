"""The KV pool stays where it is: every jitted step writes and reads the
WHOLE pool in place (``model_runner._layer_loop``, PR 24).

Two properties per step (decode, prefill, verify) x arch (gpt, gptj), and
the same three bodies under the tensor-parallel mesh at tp=2 on host devices; the first
also for the program that carries a chunk AND the slots' rows (ISSUE 47;
``tests/test_llm_joint_step.py`` holds its writes to the two programs').  A prefill
chunk and a decode write WHOLE blocks (``model_runner._scatter_kv_blocks``),
a verify window rows (``_scatter_kv``): all are held to the same reference,
the chunk at a block of 16 and a chunk of 128 for every ``start`` x
``n_valid`` of ``STARTS`` x ``N_VALID`` (a step named
``prefill@<start>+<n_valid>``):

* **no pool-sized temporary** — the compiled step's
  ``memory_analysis().temp_size_in_bytes`` stays under half of ONE pool's
  bytes at a pool made large against the model.  With the pool riding
  the layer scan as ``xs``/``ys`` (the parent of PR 24) XLA slices a layer
  out, scatters, and stacks the layers into a second buffer: the
  temporary is then the size of both pools, on this CPU backend as on
  the v5e, so the temp size is what is asserted (not the HLO text).
* **right rows, right layer, nothing else** — on a pool filled with
  noise (every element distinct: a stray write shows, and so does a read
  from another layer's blocks) a step changes the pool ONLY at ``(every
  layer, phys, :, off, :)`` of the rows it was fed, and layer ``l``'s
  rows hold layer ``l``'s k/v: the reference is a plain Python loop over
  layers on per-layer pools with ``.at[].set`` and the unshifted block
  tables, in float32.  That catches an ``l * NB`` offset applied to the
  wrong table, or a write into the wrong layer.  A chunk leaves the trash
  block as it was (a padded row writes nothing), and no block before
  ``start // block`` is touched: those may be another sequence's too.

Last, the trap beside ``_scatter_kv``'s index: the scatter whose window
spans heads compiles to a pool-sized temporary, the two forms in use do not.
"""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from ray_tpu.llm.model_runner import (  # noqa: E402
    PagedModelRunner,
    _chunk_blocks,
    _chunk_write,
    _layernorm,
    _sample_rows,
    _scatter_kv,
    _slots_write,
    _verify_rows,
    host_batch,
    pack_knobs,
)
from ray_tpu.llm.multichip import TensorParallelPagedModelRunner  # noqa: E402
from ray_tpu.models.gpt import GPTConfig, gpt_init  # noqa: E402
from ray_tpu.models.gptj import GPTJConfig, gptj_init  # noqa: E402
from ray_tpu.ops import paged_attention as pa  # noqa: E402
from ray_tpu.parallel.mesh import make_tp_mesh  # noqa: E402

# a pool large against the model: 3 layers x 512 blocks of 4 tokens, 4 heads
# x 16 = 1.5 MB a pool in float32, against ~0.2 MB of temporaries for the rest
L, NB, BS, TMAX, HEADS, HD = 3, 512, 4, 6, 4, 16
SLOTS, CHUNK, W = 4, 8, 3
# the chunk's own cases: a block of 16, a chunk of 128 (the served sizes), a
# table of 15 blocks, so that a chunk from 112 ends in the table's LAST block
# and its ninth block lies past the table's reach
WIDE = (16, 128, 15)
STARTS = (0, 5, 16, 21, 112)
N_VALID = (1, 15, 16, 17, 127, 128)
ARCHS = {
    "gpt": (
        GPTConfig(
            vocab_size=96, d_model=HEADS * HD, n_layers=L, n_heads=HEADS,
            seq_len=256, dtype="float32",
        ),
        gpt_init,
    ),
    "gptj": (
        GPTJConfig(
            vocab_size=96, seq_len=256, d_model=HEADS * HD, n_layers=L,
            n_heads=HEADS, rotary_dim=8, dtype="float32", remat=False,
            attn_impl="xla", fused_loss=False,
        ),
        gptj_init,
    ),
}
STEPS = ("decode", "prefill", "verify")
CHUNKS = tuple(f"prefill@{s}+{n}" for s in STARTS for n in N_VALID)


def _cases(steps):
    two_devices = pytest.mark.skipif(
        len(jax.devices("cpu")) < 2,
        reason="needs 2 host devices (conftest's XLA_FLAGS)",
    )
    return [(step, arch, 1) for step in steps for arch in ARCHS] + [
        pytest.param(step, "gptj", 2, marks=two_devices) for step in steps
    ]


def _geometry(step):
    """(block, chunk, table width) of a step's case."""
    return WIDE if "@" in step else (BS, CHUNK, TMAX)


@functools.lru_cache(maxsize=None)
def _params(arch):
    cfg, init = ARCHS[arch]
    return init(jax.random.PRNGKey(0), cfg)


def _noise_pools(tp, bs=BS):
    shape = (L, NB, HEADS, bs, HD)
    k = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), shape, jnp.float32)
    if tp > 1:
        sharding = NamedSharding(make_tp_mesh(tp), P(None, None, "tp"))
        k, v = jax.device_put(k, sharding), jax.device_put(v, sharding)
    return k, v


def _operands(step):
    """The step's operands after (params, k_pool, v_pool), and the rows it
    feeds as (positions (n,), phys (n,), off (n,)) — the last an
    independent statement of where a row's k/v belongs."""
    rng = np.random.default_rng(7)
    greedy = (
        np.zeros(SLOTS, np.float32), np.zeros(SLOTS, np.int32),
        np.ones(SLOTS, np.float32), np.zeros(SLOTS, np.uint32),
        np.zeros(SLOTS, np.int32),
    )
    if step.startswith("prefill"):
        bs, chunk, tmax = _geometry(step)
        table = rng.choice(np.arange(1, NB), tmax, replace=False).astype(np.int32)
        start, n_valid = 5, chunk - 2   # mid-block start; two padded rows
        if "@" in step:
            start, n_valid = map(int, step.split("@")[1].split("+"))
        tokens = rng.integers(0, 96, chunk).astype(np.int32)
        pos = start + np.arange(chunk)
        # a padded row belongs nowhere: the reference sends it to trash
        phys = np.where(
            np.arange(chunk) < n_valid, table[np.minimum(pos // bs, tmax - 1)], 0
        )
        ops = (tokens, np.int32(start), np.int32(n_valid), table)
        return ops, (pos, phys, pos % bs)
    tables = rng.choice(np.arange(1, NB), (SLOTS, TMAX), replace=False).astype(np.int32)
    if step == "decode":
        # slot 3 is inactive: position 0, an all-trash table
        positions = np.array([0, 5, 11, 0], np.int32)
        tables[3] = 0
        tokens = rng.integers(0, 96, SLOTS).astype(np.int32)
        phys = tables[np.arange(SLOTS), positions // BS]
        return (tokens, positions, tables) + greedy, (positions, phys, positions % BS)
    # verify: slot 2's window runs past the table's reach (-> trash)
    base = np.array([0, 6, TMAX * BS - 2, 9], np.int32)
    tokens = rng.integers(0, 96, (SLOTS, W)).astype(np.int32)
    pos = (base[:, None] + np.arange(W)[None, :]).reshape(-1)
    row_tables = np.repeat(tables, W, axis=0)
    logical = np.minimum(pos // BS, TMAX - 1)
    phys = np.where(
        pos < TMAX * BS, row_tables[np.arange(SLOTS * W), logical], 0
    )
    return (tokens, base, tables) + greedy, (pos, phys, pos % BS)


@functools.lru_cache(maxsize=None)
def _runner(arch, tp, bs=BS):
    """One runner a (arch, tp, block): its jitted steps compile once for all
    the cases that call them (``start`` and ``n_valid`` are traced)."""
    cfg, _ = ARCHS[arch]
    if tp > 1:
        return TensorParallelPagedModelRunner(cfg, _params(arch), bs, "xla", tp=tp)
    return PagedModelRunner(cfg, _params(arch), bs, "xla")


def _jitted(runner, step, ops):
    """(jitted fn, its operands after the pools) of a step, as the runner's
    wrappers call it: the decode takes its batch as slot state and a patch
    (``host_batch``), the prefill a sampler row."""
    if step == "decode":
        return runner._decode, host_batch(*ops)
    if step.startswith("prefill"):
        return runner._prefill, ops + (pack_knobs(0, 0.0, 0, 1.0, 0),)
    return runner._verify, ops


@pytest.mark.parametrize("step,arch,tp", _cases(STEPS + ("prefill@5+127",)))
def test_step_holds_no_pool_sized_temporary(step, arch, tp):
    bs = _geometry(step)[0]
    runner = _runner(arch, tp, bs)
    k, v = _noise_pools(tp, bs)
    ops, _rows = _operands(step)
    fn, ops = _jitted(runner, step, ops)
    compiled = fn.lower(runner.params, k, v, *ops).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    pool_bytes = k.nbytes // tp  # one pool's bytes on one device
    assert temp < pool_bytes / 2, (
        f"{step}/{arch}/tp{tp}: {temp} B of temporaries against a pool of "
        f"{pool_bytes} B: the step copies the pool"
    )


@pytest.mark.parametrize("chunk_step", ["prefill", "prefill@5+127"])
@pytest.mark.parametrize("arch", list(ARCHS))
def test_joint_step_holds_no_pool_sized_temporary(arch, chunk_step):
    """The program of a step that carries a chunk (the slots' rows and the
    chunk's rows in one layer loop: two writes and two attentions on the
    same carried pools) at the tests' sizes and at the served block and
    chunk: as in place as the two programs it stands for."""
    bs = _geometry(chunk_step)[0]
    runner = _runner(arch, 1, bs)
    k, v = _noise_pools(1, bs)
    decode = host_batch(*_operands("decode")[0])
    chunk = _operands(chunk_step)[0] + (pack_knobs(0, 0.0, 0, 1.0, 0),)
    compiled = runner._prefill_with_slots.lower(
        runner.params, k, v, *decode, *chunk
    ).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < k.nbytes / 2, (
        f"{chunk_step}/{arch}: {temp} B of temporaries against a pool of "
        f"{k.nbytes} B: the joint step copies the pool"
    )


@functools.lru_cache(maxsize=None)
def _reference(arch, step):
    """The same step as a plain Python loop over layers: layer ``l``
    scatters into and attends over ITS pool ``k_pool[l]`` with the
    tables as given.  Single-chip float32; the layer math is the
    runner's own helpers (not under test here; the two row-parallel
    products come without their bias, added here where a layer adds it,
    after the residual), the loop, the writes and the reads are not.  (One a (arch, step): tp 1 and 2 share it.)"""
    cfg, _ = ARCHS[arch]
    params = _params(arch)
    bs = _geometry(step)[0]
    ops, rows = _operands(step)
    k_pool, v_pool = _noise_pools(1, bs)
    ref = PagedModelRunner(cfg, params, bs, "xla")
    params = ref.params  # as the helpers take it (q / k / v one leaf)
    pos, phys, off = (jnp.asarray(a, jnp.int32) for a in rows)
    n = pos.shape[0]
    tokens = jnp.asarray(ops[0]).reshape(-1)
    x = ref._embed(params, tokens, pos)
    for l in range(L):
        layer = jax.tree_util.tree_map(lambda a: a[l], params["blocks"])
        ln1 = _layernorm(x, layer["ln1"]["scale"], layer["ln1"]["bias"])
        q, kr, vr = ref._qkv_rows(layer, ln1, pos)
        heads = jnp.arange(HEADS)[None, :]
        k_l = k_pool[l].at[phys[:, None], heads, off[:, None], :].set(kr)
        v_l = v_pool[l].at[phys[:, None], heads, off[:, None], :].set(vr)
        if step == "decode":
            att = pa.paged_attention_xla(q, k_l, v_l, ops[2], pos + 1)
        elif step.startswith("prefill"):
            att = pa.paged_prefill_attention_xla(q, k_l, v_l, ops[3], pos)
        else:
            att = pa.paged_verify_attention_xla(
                q.reshape(SLOTS, W, HEADS, HD), k_l, v_l, ops[2],
                pos.reshape(SLOTS, W),
            )
        att = ref._attn_out(layer, att.reshape(n, HEADS * HD))
        if arch == "gptj":
            x = x + (att + ref._mlp(layer, ln1)) + layer["mlp_out"]["bias"]
        else:
            h = x + att + layer["attn_out"]["bias"]
            x = h + ref._mlp(
                layer, _layernorm(h, layer["ln2"]["scale"], layer["ln2"]["bias"])
            ) + layer["mlp_out"]["bias"]
        k_pool, v_pool = k_pool.at[l].set(k_l), v_pool.at[l].set(v_l)
    if step.startswith("prefill"):
        out = (ref._lm_head(params, x[int(ops[2]) - 1][None, :])[0],)
    elif step == "decode":
        out = _sample_rows(ref._lm_head(params, x), ops[6], ops[7], *ops[3:6])
    else:
        logits = ref._lm_head(params, x).reshape(SLOTS, W, -1)
        out = _verify_rows(logits, ops[0][:, 1:], ops[6], ops[7], *ops[3:6])
    return np.asarray(k_pool), np.asarray(v_pool), out


@pytest.mark.parametrize("step,arch,tp", _cases(STEPS + CHUNKS))
def test_step_writes_only_its_rows_in_their_layer(step, arch, tp):
    bs = _geometry(step)[0]
    runner = _runner(arch, tp, bs)
    k0, v0 = (np.asarray(a) for a in _noise_pools(1, bs))
    ops, rows = _operands(step)
    ref_k, ref_v, ref_out = _reference(arch, step)
    fn, sent = _jitted(runner, step, ops)
    k, v = _noise_pools(tp, bs)
    k1, v1, *out = fn(runner.params, k, v, *sent)
    if step == "decode":
        # the carry the next step feeds from: the sampled token, one
        # position and one counter on
        carry, *out = out
        np.testing.assert_array_equal(
            np.asarray(carry), np.stack([np.asarray(out[0]), ops[1] + 1, ops[7] + 1])
        )

    _pos, phys, off = rows
    fed = np.zeros((L, NB, bs), bool)
    fed[:, phys, off] = True
    chunk = step.startswith("prefill")
    if chunk:
        # whole blocks: a padded row writes nothing, trash stays as it was
        fed[:, 0] = False
    # splitting the row-parallel sums over devices moves activations by an
    # ulp a layer (llm.multichip's module text); one device adds none
    tol = 1e-4 if tp > 1 else 1e-5
    for name, before, after, want in (("k", k0, k1, ref_k), ("v", v0, v1, ref_v)):
        after, want = np.asarray(after), np.asarray(want)
        changed = after != before                      # (L, NB, H, BS, D)
        assert not changed.any(axis=(2, 4))[~fed].any(), (
            f"{name}: written outside the rows fed, at (layer, block, offset) "
            f"{np.argwhere(changed.any(axis=(2, 4)) & ~fed)[:4].tolist()}"
        )
        assert changed.all(axis=(2, 4))[fed].all(), f"{name}: a fed row kept its noise"
        if chunk:
            # the blocks before the chunk's first may be ANOTHER sequence's
            # too (a shared prefix): bit for bit as they were
            shared = ops[3][: int(ops[1]) // bs]
            np.testing.assert_array_equal(after[:, shared], before[:, shared])
        # block 0 is the trash block: several rows may land on one place
        np.testing.assert_allclose(
            after[:, 1:], want[:, 1:], rtol=tol, atol=tol,
            err_msg=f"{name}: a layer's rows do not hold that layer's k/v",
        )
    for got, want in zip(out, ref_out):
        got, want = np.asarray(got), np.asarray(want)
        if np.issubdtype(got.dtype, np.integer):
            np.testing.assert_array_equal(got, want)
        else:
            np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)


def test_chunk_blocks_send_every_padded_block_to_trash():
    """One valid row: the chunk's other eight blocks hold no valid position
    and are block 0, whatever the table says; so is a block past the table's
    reach.  A start inside a block keeps the rows before it out of the mask."""
    bs, chunk, tmax = WIDE
    table = np.arange(100, 100 + tmax, dtype=np.int32)
    ids, shift, mask = _chunk_blocks(table, np.int32(21), np.int32(1), chunk, bs)
    assert np.asarray(ids).tolist() == [101] + [0] * 8 and int(shift) == 5
    assert np.argwhere(np.asarray(mask)[:, 0, :, 0]).tolist() == [[0, 5]]
    ids, _shift, mask = _chunk_blocks(table, np.int32(112), np.int32(128), chunk, bs)
    assert np.asarray(ids).tolist() == list(range(107, 115)) + [0]
    assert np.asarray(mask)[:8].all() and not np.asarray(mask)[8].any()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("start", STARTS + (37,))
def test_whole_blocks_leave_the_pool_the_rows_leave(start, dtype):
    """The two forms' contract, without a model around them: the same
    operands in ``_layer_loop``'s view (a layer's ``base`` added), the same
    pool afterwards bit for bit, in the pool's served dtype too.  The trash
    block aside: rows overwrite it, whole blocks leave it as it was."""
    bs, chunk, tmax = WIDE
    base = 2 * NB
    pool = jax.random.normal(jax.random.PRNGKey(3), (L * NB, HEADS, bs, HD)).astype(dtype)
    vals = jax.random.normal(jax.random.PRNGKey(4), (chunk, HEADS, HD)).astype(dtype)
    table = np.random.default_rng(5).choice(np.arange(1, NB), tmax, replace=False)
    pos = start + np.arange(chunk)
    for n_valid in (0,) + N_VALID:
        phys = np.where(
            np.arange(chunk) < n_valid, table[np.minimum(pos // bs, tmax - 1)], 0
        ).astype(np.int32)
        want = np.array(_scatter_kv(pool, vals, base + phys, pos % bs))
        write = _chunk_write(
            table.astype(np.int32), np.int32(start), np.int32(n_valid), chunk, bs
        )
        want[base] = np.asarray(pool[base])
        np.testing.assert_array_equal(
            np.asarray(write(pool, vals, base)), want, err_msg=f"n_valid={n_valid}"
        )


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_decode_s_blocks_leave_the_pool_its_rows_leave(dtype):
    """The same contract for one position of many sequences: every live
    slot's row lands where the rows' form puts it, and nothing else of its
    block moves; the empty slots (position 0, an all-trash table) meet in
    the trash block, where either form may leave any of their rows."""
    bs, slots, base = WIDE[0], 32, NB
    pool = jax.random.normal(jax.random.PRNGKey(3), (L * NB, HEADS, bs, HD)).astype(dtype)
    vals = jax.random.normal(jax.random.PRNGKey(4), (slots, HEADS, HD)).astype(dtype)
    rng = np.random.default_rng(6)
    phys = rng.choice(np.arange(1, NB), slots, replace=False).astype(np.int32)
    off = rng.integers(0, bs, slots).astype(np.int32)
    phys[::5], off[::5] = 0, 0
    want = np.array(_scatter_kv(pool, vals, base + phys, off))
    got = np.array(_slots_write(phys, off, bs)(pool, vals, base))
    want[base] = got[base] = 0
    np.testing.assert_array_equal(got, want)


def _wide_window(pool, vals, phys, off):
    """The index a reader would write first: one scatter index a ROW, its
    update window over (heads, d)."""
    return pool.at[phys, :, off, :].set(vals)


def _as_blocks(pool, vals, phys, off):
    table = jnp.arange(1, 1 + WIDE[2], dtype=jnp.int32)
    return _chunk_write(table, off[0], vals.shape[0], WIDE[1], WIDE[0])(pool, vals, 0)


def _as_slots(pool, vals, phys, off):
    return _slots_write(phys, off, WIDE[0])(pool, vals, 0)


@pytest.mark.parametrize(
    "form,copies",
    [(_scatter_kv, False), (_as_blocks, False), (_as_slots, False), (_wide_window, True)],
    ids=["rows", "chunk_blocks", "slot_blocks", "wide_window"],
)
def test_scatter_over_heads_would_copy_the_pool(form, copies):
    """Why ``_scatter_kv`` carries ``arange(heads)`` in its index: without
    it XLA transposes the pool (a temporary of one pool here, 2,114,025,984
    B on a v5e at the one-chip cell's size); the rows' index and the whole
    blocks of a chunk or a decode stay in place.  float32: in bfloat16 the CPU
    backend reports two pools for all three."""
    bs, chunk, _tmax = WIDE
    pool = jax.ShapeDtypeStruct((L * NB, HEADS, bs, HD), jnp.float32)
    vals = jax.ShapeDtypeStruct((chunk, HEADS, HD), jnp.float32)
    rows = jax.ShapeDtypeStruct((chunk,), jnp.int32)
    compiled = jax.jit(form, donate_argnums=0).lower(pool, vals, rows, rows).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    pool_bytes = np.prod(pool.shape) * 4
    # 1.00 pool for the wide window; 0.008 for the rows' index, 0.009 for a
    # chunk's blocks, 0.083 for the blocks of 128 slots (the gathered blocks)
    assert (temp > 0.9 * pool_bytes) if copies else (temp < 0.15 * pool_bytes), (
        f"{temp} B of temporaries against a pool of {pool_bytes} B"
    )
