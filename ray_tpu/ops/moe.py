"""A DROPLESS expert layer over the experts ONE chip holds of a layer that
is spread over many (expert parallelism, this chip's share: 12 of 384 held,
Kimi-K2.5; 36 of 72, Granite-4.0-H), or ALL of it (``offset`` 0, ``held`` the
router's whole width: LFM2's 64 of 64, where nothing is left out and the
"share" below is the layer).

The router scores every token over ALL of the layer's experts and chooses
``top_k`` of them (``route``: sigmoid scores and a selection bias;
``route_logits``: the largest logits, a softmax over the chosen; a family
calls the one its model has); this chip holds experts ``offset .. offset +
held`` and computes THEIR part of the result: ``sum over chosen AND held e
of w_e * Expert_e(x)``.  What the absent experts would add is left out (it
is the other chips' part); nothing here stands in for them.

No capacity and no drop: every (token, held expert) pair the router chose is
computed, whatever the load's shape (``models.gpt._moe_mlp``, the training
path's layer, drops what exceeds a fixed capacity and so matches no
reference; this one does).  Shapes stay static all the same, and an expert
no row chose is never read.  ``expert_layer`` has TWO forms, and the batch's
rows ``n`` against ``tile`` choose between them -- the shape, no family's
name, no option:

* ``n > tile`` (a prefill chunk: 512 rows, 71 pairs an expert), the TILE
  LOOP: the pairs of one expert go through its weights in tiles of ``tile``
  rows, gathered by a sort of the mask and scattered back, and the loop over
  tiles is as long as the load says (a ``fori_loop`` with a traced bound).
  Where a chip holds the whole layer the pairs spread thinner: 512 rows x 4
  of LFM2's 64 experts are 32 an expert, ONE tile of 64 rows HALF full
  (``tile_rows`` over a chunk's load: ``stats()["moe"]["chunk_tile_rows"]``),
  and every expert is touched, so a chunk reads the layer whole.
* ``n <= tile`` (every decode: 16 rows), the BATCH FORM: no tile is made.  A
  touched expert sees ALL ``n`` rows and the router's weight column does the
  selecting, ``out = sum over touched e, in expert order, of where(mask[:,
  e], wmat[:, e], 0)[:, None] * swiglu(x, W_e)``: no sort, no gather, no
  scatter; ``x`` stays where it is and ``out`` is an accumulator that is
  never indexed.  A row gets an exact ``+0.0`` from an expert it did not
  choose, so its result does not depend on what the other rows chose.  The
  experts to visit are a compacted list made once a layer from ``load > 0``
  (``touched``).  On a TPU it is ONE Pallas kernel a layer
  (``moe_batch_experts``, the pattern of ``ops.ssd``): the list is scalar
  prefetch, a weight block's index is ``first + ids[i]`` in the flat arrays
  of every layer's experts, the grid is the static ``(experts held, blocks
  of f)``; a step past the list's end names the block before it (nothing is
  fetched) and computes nothing (a layer whose list is EMPTY still names
  one block of its first expert: the one read no row asked for).  Pallas'
  double buffering has expert ``i + 1``'s weights in flight while expert
  ``i`` multiplies, which a loop of three XLA dots on dynamically indexed
  operands does not do: with 36 experts of 18.9 MB a layer (Granite-4.0-H)
  a decode IS these reads, 37 us a touched expert through the tile loop
  where the bytes are 23 (PR 59), 25 through the kernel (PR 60).  The
  compacted list is what keeps the static grid honest where 16 rows x 4 touch
  41 of 64 held experts (LFM2): a third of the grid's steps fetch nothing.
  Elsewhere (``impl="xla"``, and ``auto`` off a TPU) the same form is a
  ``fori_loop`` of plain ``jax.numpy``.

The kernel's weight blocks are cut along ``f`` alone (``block_f``): the
widest multiple of 128 lanes that divides ``f`` and keeps two buffers of the
three matrices' blocks inside ``VMEM_BUDGET``.  An expert of 3 x 4096 x 768
bfloat16 (18.9 MB) goes whole, every product one dot and every read
contiguous; one of 3 x 7168 x 2048 (88 MB, Kimi-K2.5) goes in blocks of
``f``, the down product's partial sums added in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops.paged_attention import _on_tpu

#: rows a tile of ``expert_layer`` holds; a batch of no more rows makes no
#: tile at all (the batch form)
TILE = 64
#: VMEM the batch kernel's weight blocks may take: two buffers of the three
#: matrices' blocks (a v5e has 128 MiB; ``x``, the accumulator and the
#: products' results are under 2 MB beside them)
VMEM_BUDGET = 64 << 20


def route(x32, router_kernel, select_bias, top_k: int, scaling: float, eps: float = 1e-20):
    """Sigmoid scores over every expert, ``top_k`` chosen by ``score +
    select_bias`` (the bias chooses and does not weigh), weights the chosen
    scores over their sum plus ``eps`` (1e-20 is DeepSeek-V3's and
    Kimi-K2.5's; LFM2 publishes 1e-6), times ``scaling``.  All float32, the
    product at ``highest`` precision: the choice is discontinuous in the scores and
    the 8th and 9th lie about 0.05 apart.  x32: (N, d) float32.  Returns
    (chosen (N, top_k) int32, weights (N, top_k) float32)."""
    z = jnp.dot(x32, router_kernel.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.sigmoid(z)
    _, chosen = jax.lax.top_k(p + select_bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(p, chosen, axis=-1)
    return chosen, picked / (picked.sum(-1, keepdims=True) + eps) * scaling


def route_logits(x32, router_kernel, top_k: int):
    """The second router (Granite-4.0-H's): the ``top_k`` largest LOGITS over
    every expert chosen, weights a softmax over the chosen logits alone: no
    sigmoid, no selection bias, no scaling factor.  All float32, the product
    at ``highest`` precision.  x32: (N, d) float32.  Returns (chosen (N,
    top_k) int32, weights (N, top_k) float32, summing to 1)."""
    z = jnp.dot(x32, router_kernel.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
    picked, chosen = jax.lax.top_k(z, top_k)
    return chosen, jax.nn.softmax(picked, axis=-1)


def held_pairs(chosen, weights, offset: int, held: int, live):
    """The router's choice as this chip sees it: (mask (N, held) bool, the
    token chose held expert e; wmat (N, held) float32, with what weight).
    A row that is not ``live`` (a dead decode row, a chunk's padding) has no
    pair."""
    local = chosen[:, :, None] - offset == jnp.arange(held)[None, None, :]
    local = local & live[:, None, None]
    return local.any(axis=1), (local * weights[:, :, None]).sum(axis=1)


def swiglu(x, gate, up, down):
    """``(silu(x gate) * (x up)) down``: products on x's dtype, sums, the
    activation and the result in float32."""
    dot = lambda a, k: jnp.dot(  # noqa: E731
        a, k.astype(a.dtype), preferred_element_type=jnp.float32)
    return dot((jax.nn.silu(dot(x, gate)) * dot(x, up)).astype(x.dtype), down)


def tile_rows(load, n: int, tile: int = TILE):
    """The rows ``expert_layer`` computes for a ``load`` (pairs by held
    expert) over ``n`` rows, in either form: every tile is whole, whatever it
    holds, and the batch form gives a touched expert all ``n`` rows (what one
    tile of ``min(tile, n)`` rows was)."""
    tile = min(tile, n)
    return ((load + (tile - 1)) // tile).sum().astype(jnp.int32) * tile


def batch_steps(load, n: int, tile: int = TILE):
    """The expert steps the BATCH FORM makes for a ``load`` over ``n`` rows:
    one a touched expert where ``n <= tile``; none where the tile loop
    runs."""
    return (load > 0).sum().astype(jnp.int32) * int(n <= tile)


def touched(mask):
    """The experts some row chose, compacted, in expert order: (ids (E,)
    int32, how many).  Entries past the count repeat the last touched expert
    (0 where there is none), so a kernel's step there names the block it
    already holds.  A comparison of a running count: no sort, no scatter."""
    experts = mask.shape[1]
    hit = mask.any(axis=0)
    upto = jnp.cumsum(hit.astype(jnp.int32))
    at = jnp.arange(experts, dtype=jnp.int32)
    ids = (upto[None, :] <= at[:, None]).sum(axis=1).astype(jnp.int32)
    return jnp.minimum(ids, jnp.where(hit, at, 0).max()), upto[-1]


def block_f(d: int, f: int, itemsize: int, budget: int = VMEM_BUDGET) -> int:
    """Columns of ``f`` a grid step of the batch kernel holds of each matrix:
    all of ``f`` where two buffers of the three ``d x f`` matrices fit the
    budget, else the widest multiple of 128 that divides ``f`` and does."""
    fits = lambda bf: 2 * 3 * d * bf * itemsize <= budget  # noqa: E731
    if fits(f):
        return f
    cuts = [bf for bf in range(128, f, 128) if f % bf == 0 and fits(bf)]
    if not cuts:
        raise ValueError(f"no block of an expert of {d} x {f} fits {budget} bytes of VMEM")
    return cuts[-1]


def _batch_xla(x, wsel, ids, count, gate, up, down, first):
    """The batch form in plain ``jax.numpy``: one ``swiglu`` of ALL rows a
    touched expert, weighed by its column ``wsel[e]`` (E, N)."""
    at = lambda k, e: jax.lax.dynamic_index_in_dim(  # noqa: E731
        k, first + e, 0, keepdims=False)

    def one_expert(i, out):
        e = ids[i]
        y = swiglu(x, at(gate, e), at(up, e), at(down, e))
        return out + jax.lax.dynamic_index_in_dim(wsel, e, 0, keepdims=False)[:, None] * y

    return jax.lax.fori_loop(0, count, one_expert, jnp.zeros(x.shape, jnp.float32))


def _batch_kernel(ids_ref, meta_ref, x_ref, w_ref, gate_ref, up_ref, down_ref, o_ref):
    """One (touched expert, block of ``f``): ``x_ref`` (N, d) and ``o_ref``
    (N, d) float32 stay in VMEM through the whole grid; ``w_ref`` (1, N, 1) is
    the expert's weight column, ``gate_ref`` / ``up_ref`` (1, d, bf) and
    ``down_ref`` (1, bf, d) its blocks.  ``swiglu`` on the block: the
    activation is elementwise in ``f``, the down product's partial sums add in
    float32."""
    from jax.experimental import pallas as pl

    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(i < meta_ref[0])
    def _():
        o_ref[...] += w_ref[0] * swiglu(x_ref[...], gate_ref[0], up_ref[0], down_ref[0])


def _batch_pallas(x, wsel, ids, count, gate, up, down, first, *, interpret: bool,
                  bf: int | None = None):
    """The batch form as ONE kernel over the compacted list.  Rows are padded
    to whole sublane tiles of ``x``'s dtype (16 rows of bfloat16 are one)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (n, d), experts, f = x.shape, wsel.shape[0], gate.shape[-1]
    size = gate.dtype.itemsize
    bf = bf or block_f(d, f, size)
    steps = f // bf
    pad = -n % (32 // x.dtype.itemsize)
    x = jnp.pad(x, ((0, pad), (0, 0)))
    w = jnp.pad(wsel, ((0, 0), (0, pad)))[:, :, None]
    rows = n + pad

    # expert ``ids[i]`` of this layer, block ``j`` of f; a step past the
    # list's end names the block the step before it held
    def place(i, j, ids, meta):
        return meta[1] + ids[i], jnp.where(i < meta[0], j, steps - 1)

    def columns(i, j, ids, meta):
        e, b = place(i, j, ids, meta)
        return e, 0, b

    def rows_of(i, j, ids, meta):
        e, b = place(i, j, ids, meta)
        return e, b, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(experts, steps),
        in_specs=[
            pl.BlockSpec((rows, d), lambda i, j, ids, meta: (0, 0)),
            pl.BlockSpec((1, rows, 1), lambda i, j, ids, meta: (ids[i], 0, 0)),
            pl.BlockSpec((1, d, bf), columns),
            pl.BlockSpec((1, d, bf), columns),
            pl.BlockSpec((1, bf, d), rows_of),
        ],
        out_specs=pl.BlockSpec((rows, d), lambda i, j, ids, meta: (0, 0)),
    )
    out = pl.pallas_call(
        _batch_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the three matrices' blocks, double-buffered, and the rest
            vmem_limit_bytes=2 * 3 * d * bf * size + (16 << 20),
        ),
        interpret=interpret,
        name="moe_batch_experts",
    )(ids, jnp.stack([count, jnp.asarray(first, jnp.int32)]), x, w, gate, up, down)
    return out[:n]


def expert_layer(x, mask, wmat, gate, up, down, *, first=0, tile: int = TILE,
                 impl: str = "auto"):
    """``sum_e wmat[:, e] * Expert_e(x)`` over exactly the pairs in ``mask``.
    x: (N, d) in the products' dtype; mask, wmat: (N, E); gate, up: (.., d,
    f), down: (.., f, d): expert e's weights at ``first + e`` (``first`` may
    be traced: the experts of EVERY layer in one array, so that a layer
    loop hands this one no slice of them -- XLA would copy it, all of a
    layer's experts a layer).  Returns (N, d) float32; experts are added in
    order, so the sum does not depend on the load's shape.  ``N <= tile``
    takes the batch form (``impl``: ``pallas`` the kernel, ``xla`` plain
    ``jax.numpy``, ``auto`` the kernel on a TPU), ``N > tile`` the tile loop
    whatever ``impl`` says."""
    if impl not in ("auto", "xla", "pallas"):
        raise ValueError(f"unknown expert impl {impl!r}; expected 'auto', 'xla' or 'pallas'")
    n, experts = mask.shape
    if n <= tile:
        ids, count = touched(mask)
        args = (x, jnp.where(mask, wmat, 0.0).T, ids, count, gate, up, down, first)
        if impl == "xla" or (impl == "auto" and not _on_tpu()):
            return _batch_xla(*args)
        return _batch_pallas(*args, interpret=not _on_tpu())
    counts = mask.sum(axis=0).astype(jnp.int32)                  # (E,)
    # per expert, the tokens that chose it first, in token order; a tile's
    # slice may run past N where N is no multiple of the tile
    order = jnp.argsort(~mask, axis=0, stable=True).T.astype(jnp.int32)
    order = jnp.pad(order, ((0, 0), (0, tile)))
    tiles = (counts + (tile - 1)) // tile
    ends = jnp.cumsum(tiles)

    def one_tile(t, out):
        e = jnp.searchsorted(ends, t, side="right").astype(jnp.int32)
        row0 = (t - (ends[e] - tiles[e])) * tile
        rows = jax.lax.dynamic_slice(order, (e, row0), (1, tile))[0]
        valid = row0 + jnp.arange(tile, dtype=jnp.int32) < counts[e]
        rows = jnp.where(valid, rows, 0)
        at = lambda k: jax.lax.dynamic_index_in_dim(  # noqa: E731
            k, first + e, 0, keepdims=False)
        y = swiglu(x[rows], at(gate), at(up), at(down))
        w = jnp.where(valid, wmat[rows, e], 0.0)
        return out.at[jnp.where(valid, rows, n)].add(y * w[:, None], mode="drop")

    return jax.lax.fori_loop(0, ends[-1], one_tile, jnp.zeros(x.shape, jnp.float32))
