"""Runtime twins for raylint's mesh/SPMD phase (RL020, RL024).

Per the test_core_races.py precedent: the static rule flags a bug shape,
and the twin PROVES the same shape actually fails (or silently retraces)
on a real multi-device mesh — static and runtime pointing at the same
line. RL020's shape (a collective axis no enclosing shard_map binds)
raises ``NameError: unbound axis name`` at TRACE time; RL024's shape (a
single-device placement flowing into a mesh-jitted call) produces no
exception at all — only a second compile-cache entry, which is exactly
why it needed a lint rule (the PR 13 bug ran for a whole session at 2x
step time before anyone noticed).
"""

import numpy as np
import pytest


def _multi_device_cpu() -> bool:
    """Capability probe: the twins need a >=2-device CPU mesh. The
    suite's conftest forces 8 in-process CPU devices before jax
    initializes; the probe checks the devices actually materialized,
    without mutating anything."""
    import jax

    return len(jax.devices("cpu")) >= 2


pytestmark = pytest.mark.skipif(
    not _multi_device_cpu(),
    reason="needs a >=2-device CPU mesh "
    "(XLA_FLAGS=--xla_force_host_platform_device_count, set by conftest)",
)


def _mesh(n=2):
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices("cpu")[:n]), ("data",))


# --------------------------------------------------------------------- RL020


def test_rl020_unbound_axis_raises_at_trace_time():
    """The RL020 bug shape: ``psum(x, "dp")`` with no enclosing shard_map
    binding "dp" dies the FIRST time the function is traced — i.e. in
    whatever multi-chip path first exercises it, not where the collective
    was written. The static rule moves the diagnostic to the source."""
    import jax
    import jax.numpy as jnp

    def body(x):
        return jax.lax.psum(x, "dp")

    with pytest.raises(NameError, match="unbound axis name"):
        jax.jit(body)(jnp.ones((4,)))


def test_rl020_bound_axis_traces_clean():
    """Positive control: the identical collective under a shard_map whose
    mesh binds the axis traces and runs — it is the BINDING the rule
    checks, not the collective."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    mesh = _mesh(2)

    def body(x):
        return jax.lax.psum(x.sum(), "data")  # local sum, then cross-device

    f = jax.shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P())
    out = f(jnp.arange(4, dtype=jnp.float32))
    assert float(out) == pytest.approx(0.0 + 1.0 + 2.0 + 3.0)


# --------------------------------------------------------------------- RL024


def test_rl024_single_device_placement_bumps_compile_cache():
    """The RL024 bug shape, live: a jitted fn first called with a
    mesh-placed (NamedSharding) operand, then with the same shape/dtype
    committed to a single device. No error, no warning — just a second
    entry in ``PjitFunction._cache_size``: the committed sharding is part
    of the compile-cache key, so the drifting placement retraces and
    recompiles on call 2. In the PR 13 incident this fired EVERY step."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _mesh(2)
    f = jax.jit(lambda b: b * 2.0)
    arr = np.ones((4, 2), np.float32)

    good = jax.device_put(arr, NamedSharding(mesh, P("data")))
    f(good)
    assert f._cache_size() == 1

    bad = jax.device_put(arr, jax.devices("cpu")[0])  # the RL024 placement
    f(bad)
    assert f._cache_size() == 2  # silent recompile — the whole bug


def test_rl024_consistent_placement_reuses_cache():
    """The fixed shape (what shard_train_state does since PR 13): every
    call placed with the same NamedSharding — fresh values, one cache
    entry forever."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _mesh(2)
    sharding = NamedSharding(mesh, P("data"))
    g = jax.jit(lambda b: b * 2.0)
    arr = np.ones((4, 2), np.float32)

    g(jax.device_put(arr, sharding))
    g(jax.device_put(arr + 1.0, sharding))
    assert g._cache_size() == 1
