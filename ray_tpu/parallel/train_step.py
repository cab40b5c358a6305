"""Compiled SPMD training step.

The reference's training step is user torch code with DDP allreduce hooks
(``train/torch/train_loop_utils.py:158``); ours is one jitted function over
the mesh: forward + backward + optimizer update, with gradient reduction,
ZeRO gathers and tensor-parallel collectives all compiled by XLA SPMD from
the sharding annotations. Optimizer state inherits the parameter shardings
(ZeRO: Adam moments live scattered over ``fsdp``).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import optax
from jax.sharding import NamedSharding

from ray_tpu._private.compile_cache import ensure_compile_cache
from ray_tpu.parallel.sharding import batch_spec, param_sharding_rules


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jax.Array


def global_put(x, sharding) -> jax.Array:
    """Place a host array onto a (possibly multi-process) sharding.

    ``jax.device_put`` rejects shardings that span non-addressable devices;
    ``make_array_from_callback`` builds the global array from the shards this
    process owns, so the SAME init path serves the single-process virtual
    mesh and the real two-process DCN dryrun (every process must hold the
    same host value — true for seeded param init and test batches)."""
    if sharding.is_fully_addressable:
        return jax.device_put(x, sharding)  # no host round-trip single-process
    import numpy as np

    x = np.asarray(x)
    return jax.make_array_from_callback(x.shape, sharding, lambda idx: x[idx])


def shard_train_state(params, p_specs, optimizer, mesh) -> TrainState:
    """Place a host param tree onto the mesh per ``p_specs`` and build the
    matching sharded optimizer state (shared by build_train_step and the
    flax bridge — ONE copy of the ZeRO placement wiring)."""
    params = jax.tree_util.tree_map(
        lambda x, s: global_put(x, NamedSharding(mesh, s)), params, p_specs
    )
    opt_state = jax.jit(
        optimizer.init,
        out_shardings=_opt_shardings(optimizer, params, p_specs, mesh),
    )(params)
    import numpy as np
    from jax.sharding import PartitionSpec as P

    # the step counter is placed REPLICATED ON THE MESH like every other
    # state leaf: a bare jnp.zeros(()) carries SingleDeviceSharding, which
    # differs from the step output's NamedSharding — the jitted train step
    # then silently RETRACED (full fwd+bwd recompile) on its second call
    # (found by util.device_prof's retrace detector)
    step0 = global_put(np.zeros((), np.int32), NamedSharding(mesh, P()))
    return TrainState(params, opt_state, step0)


def make_step_fn(loss_fn, optimizer, mesh):
    """Jitted fwd+bwd+optimizer step with donated state and dp/fsdp-sharded
    batches (the step half of ``build_train_step``, reusable with any
    param-sharding source)."""

    def step(state: TrainState, batch):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(
                grads, state.opt_state, state.params
            )
            params = optax.apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1), loss

    ensure_compile_cache()
    return jax.jit(
        step,
        in_shardings=(None, NamedSharding(mesh, batch_spec())),
        donate_argnums=(0,),
    )


def profile_step_fn(step_fn, site: str = "train_step"):
    """Opt-in device-step profiling for a jitted train step: wall time
    per call into ``device_step_seconds{site=train_step}`` and runtime
    retrace detection (``train.retrace`` events + the ``device_retraces``
    counter feeding the retrace-storm SLO — ``util.device_prof``).

    A WRAPPER on purpose: ``make_step_fn``'s return stays a bare
    ``jax.jit`` call so raylint's dataflow summaries keep resolving its
    ``donate_argnums`` for use-after-donation analysis at call sites.
    The wrapped callable exposes ``.profiler`` (per-site stats) and
    ``.__wrapped__`` (the raw jitted step)."""
    import time

    from ray_tpu.util.device_prof import JitProfiler

    prof = JitProfiler(event="train.retrace")

    def profiled(state, batch):
        t0 = time.perf_counter()
        out = step_fn(state, batch)
        prof.note(site, step_fn, time.perf_counter() - t0)
        return out

    profiled.profiler = prof
    profiled.__wrapped__ = step_fn
    return profiled


def build_train_step(
    loss_fn: Callable[[Any, jax.Array], jax.Array],
    optimizer: optax.GradientTransformation,
    mesh,
):
    """Returns ``(init_fn, step_fn)``.

    ``loss_fn(params, batch) -> scalar`` is differentiated; ``init_fn(params)``
    shards params + optimizer state onto the mesh; ``step_fn(state, batch)``
    is jitted with explicit in/out shardings so it can be dispatched with zero
    host-side resharding.
    """

    def init_fn(params) -> TrainState:
        return shard_train_state(params, param_sharding_rules(params), optimizer, mesh)

    return init_fn, make_step_fn(loss_fn, optimizer, mesh)


def _opt_shardings(optimizer, params, p_specs, mesh):
    """Optimizer-state shardings: optax state subtrees (Adam mu/nu, …) mirror
    the parameter pytree, so an opt-state leaf path ends with some parameter's
    path — match by longest path suffix and inherit that param's spec (ZeRO:
    moments live scattered exactly like their parameter). Non-mirroring leaves
    (step counts, scalars) replicate."""
    from jax.sharding import PartitionSpec as P

    def path_keys(path):
        return tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)

    param_specs: dict[tuple, Any] = {}
    jax.tree_util.tree_map_with_path(
        lambda path, spec: param_specs.setdefault(path_keys(path), spec), p_specs
    )

    shapes = jax.eval_shape(optimizer.init, params)

    def one(path, leaf):
        keys = path_keys(path)
        for i in range(len(keys)):
            spec = param_specs.get(keys[i:])
            if spec is not None and len(spec) <= len(leaf.shape):
                return NamedSharding(mesh, spec)
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(one, shapes)
