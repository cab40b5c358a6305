"""Pipeline parallelism over the ``pp`` mesh axis (GPipe-style).

SURVEY §2.4 lists PP as absent from the reference ("expressible as actor
pipelines / compiled DAG channels", never implemented natively). The
TPU-first realization is NOT an actor pipeline: all ``pp`` stages live in
one pjit program; layer parameters shard over the ``pp`` axis (stage s holds
layers [s·L/pp, (s+1)·L/pp)); microbatches stream through a ``lax.scan``
over ticks where every stage processes its resident microbatch and hands
activations to its successor via ``lax.ppermute`` — the collective-permute
pipeline used by production TPU frameworks. The schedule is GPipe: M
microbatches drain in M + pp − 1 ticks (bubble fraction (pp−1)/(M+pp−1)),
and reverse-mode AD through scan+ppermute yields the backward pipeline
automatically.

``pipeline_apply`` is model-agnostic: any ``stage_fn(stage_params, x) -> y``
whose stacked parameters carry a leading layer dimension works.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _stage_slice(tree: Any, stage: jax.Array, n_stages: int, n_layers: int):
    """Dynamic-slice each stacked param (L, ...) to this stage's (L/pp, ...)."""
    per = n_layers // n_stages

    def one(leaf):
        start = (stage * per,) + (0,) * (leaf.ndim - 1)
        sizes = (per,) + leaf.shape[1:]
        return jax.lax.dynamic_slice(leaf, start, sizes)

    return jax.tree_util.tree_map(one, tree)


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stacked_params: Any,
    x: jax.Array,
    mesh,
    n_layers: int,
    microbatches: int,
    axis_name: str = "pp",
    batch_axes: tuple = (("dp", "fsdp"),),
):
    """Run ``x`` (batch, ...) through ``n_layers`` stacked layers pipelined
    over the mesh's ``pp`` axis with GPipe microbatching.

    - ``stage_fn(stage_params, x_mb)`` applies ONE stage's layers to one
      microbatch (it typically scans its local layers).
    - ``stacked_params``: pytree with leading layer dim L (sharded over pp by
      the caller's param shardings).
    - ``microbatches`` must divide the (global) batch.

    Returns activations with the same shape/sharding as ``x``.
    """
    pp = mesh.shape.get(axis_name, 1)
    if pp == 1:
        return stage_fn(stacked_params, x)
    if n_layers % pp:
        raise ValueError(f"n_layers {n_layers} must divide by pp={pp}")

    in_spec = P(*batch_axes) if batch_axes else P()
    # params enter shard_map split over pp on the LAYER dim
    param_spec = jax.tree_util.tree_map(lambda _: P(axis_name), stacked_params)

    def shard_body(params_local, x_local):
        # params_local: (L/pp, ...) this stage's layers; x_local: local batch
        stage = jax.lax.axis_index(axis_name)
        b = x_local.shape[0]
        if b % microbatches:
            raise ValueError(
                f"local batch {b} must divide into microbatches={microbatches}"
            )
        mb = b // microbatches
        xs = x_local.reshape((microbatches, mb) + x_local.shape[1:])
        n_ticks = microbatches + pp - 1
        # pad the microbatch stream with zeros for drain ticks
        pad = jnp.zeros((pp - 1,) + xs.shape[1:], xs.dtype)
        feed = jnp.concatenate([xs, pad], axis=0)

        def tick(carry, x_t):
            incoming = carry  # activations arriving from the previous stage
            x_in = jnp.where(stage == 0, x_t, incoming)
            y = stage_fn(params_local, x_in)
            # hand off to the next stage (stage pp-1's output falls off the
            # end — it is the pipeline's OUTPUT, collected in ys)
            passed = jax.lax.ppermute(
                y, axis_name, [(i, i + 1) for i in range(pp - 1)]
            )
            return passed, y

        _, ys = jax.lax.scan(tick, jnp.zeros_like(feed[0]), feed)
        # stage pp-1 emitted microbatch m at tick m + pp - 1; every stage
        # computes the same gather, but only the LAST stage's ys hold real
        # outputs — broadcast them back around the ring so every stage
        # returns identical activations (keeps downstream ops replicated
        # over pp, like the reference's last-stage-owns-loss designs avoid).
        out = ys[pp - 1 :]  # (microbatches, mb, ...)
        out = out.reshape((b,) + x_local.shape[1:])
        # broadcast the last stage's (only real) output to every stage:
        # mask+psum — one collective, keeps downstream ops replicated over pp
        out = jnp.where(stage == pp - 1, out, jnp.zeros_like(out))
        out = jax.lax.psum(out, axis_name)
        return out

    fn = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(param_spec, in_spec),
        out_specs=in_spec,
        check_vma=False,
    )
    return fn(stacked_params, x)
