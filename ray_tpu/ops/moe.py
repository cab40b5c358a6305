"""A DROPLESS expert layer over the experts ONE chip holds of a layer that
is spread over many (expert parallelism, this chip's share).

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
reference; this one does).  Shapes stay static all the same: the pairs of
one expert go through its weights in TILES of ``tile`` rows, and the loop
over tiles is as long as the load says (a ``fori_loop`` with a traced
bound): an expert no row chose has no tile, so its weights are never read --
in a decode of 16 rows about 3.5 of 12 held experts are touched a layer,
and 88 MB of weights an expert is what a decode is made of.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


#: rows a tile of ``expert_layer`` holds (a batch of fewer rows is one tile)
TILE = 64


def route(x32, router_kernel, select_bias, top_k: int, scaling: float):
    """Sigmoid scores over every expert, ``top_k`` chosen by ``score +
    select_bias`` (the bias chooses and does not weigh), weights the chosen
    scores normalised to sum 1, times ``scaling``.  All float32, the product
    at ``highest`` precision: the choice is discontinuous in the scores and
    the 8th and 9th lie about 0.05 apart.  x32: (N, d) float32.  Returns
    (chosen (N, top_k) int32, weights (N, top_k) float32)."""
    z = jnp.dot(x32, router_kernel.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.sigmoid(z)
    _, chosen = jax.lax.top_k(p + select_bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(p, chosen, axis=-1)
    return chosen, picked / (picked.sum(-1, keepdims=True) + 1e-20) * scaling


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
    """The rows ``expert_layer``'s tiles compute for a ``load`` (pairs by
    held expert) over ``n`` rows: every tile is whole, whatever it holds."""
    tile = min(tile, n)
    return ((load + (tile - 1)) // tile).sum().astype(jnp.int32) * tile


def expert_layer(x, mask, wmat, gate, up, down, *, first=0, tile: int = TILE):
    """``sum_e wmat[:, e] * Expert_e(x)`` over exactly the pairs in ``mask``.
    x: (N, d) in the products' dtype; mask, wmat: (N, E); gate, up: (.., d,
    f), down: (.., f, d): expert e's weights at ``first + e`` (``first`` may
    be traced: the experts of EVERY layer in one array, so that a layer
    loop hands the tile loop no slice of them -- XLA would copy it, all of a
    layer's experts a layer).  Returns (N, d) float32; experts are added in
    order, so the sum does not depend on the load's shape."""
    n, experts = mask.shape
    tile = min(tile, n)
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
