"""Subprocess body for the REAL multi-process multislice dryrun.

Each worker is one "slice host": it owns ``--local-devices`` virtual CPU
chips, joins the global runtime via ``jax.distributed.initialize`` (the
TPU-native counterpart of the reference building a cross-host process group
in ``python/ray/train/torch/config.py:47-91``), and participates in ONE
global mesh whose dp axis crosses the process boundary — so the dp gradient
all-reduce really rides the inter-process (DCN-equivalent) channel, here
gloo over localhost, on real pods the megascale DCN transport.

Run via ``ray_tpu.parallel.multislice.launch_multislice_procs`` (or by hand:
``python -m ray_tpu.parallel._multislice_worker --rank 0 --coord
localhost:PORT --procs 2``).
"""

from __future__ import annotations

import argparse
import os
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rank", type=int, required=True)
    ap.add_argument("--coord", required=True)
    ap.add_argument("--procs", type=int, default=2)
    ap.add_argument("--local-devices", type=int, default=4)
    ap.add_argument("--steps", type=int, default=2)
    args = ap.parse_args()

    # Must precede the first jax import in this (fresh) process.
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={args.local_devices}"
    )

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", args.local_devices)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=args.coord,
        num_processes=args.procs,
        process_id=args.rank,
    )

    import numpy as np
    import optax
    from jax.sharding import NamedSharding

    from ray_tpu.models.gpt import GPTConfig, gpt_init, gpt_loss
    from ray_tpu.parallel.mesh import MeshConfig
    from ray_tpu.parallel.multislice import make_multislice_mesh
    from ray_tpu.parallel.sharding import batch_spec
    from ray_tpu.parallel.train_step import build_train_step, global_put

    n_global = args.procs * args.local_devices
    assert len(jax.devices()) == n_global, (len(jax.devices()), n_global)
    # jax.devices() is process-major, so contiguous slice partitioning puts
    # the slice boundary exactly on the process boundary: dp's major dim
    # enumerates processes, tp stays within one process ("ICI").
    tp = 2 if args.local_devices % 2 == 0 else 1
    mesh = make_multislice_mesh(
        MeshConfig(dp=n_global // tp, fsdp=1, tp=tp, sp=1),
        num_slices=args.procs,
        devices=jax.devices(),
    )

    cfg = GPTConfig(
        vocab_size=512, seq_len=64, d_model=128, n_layers=2, n_heads=4
    )

    def loss_fn(params, batch):
        return gpt_loss(cfg, params, batch, mesh)

    init_fn, step_fn = build_train_step(loss_fn, optax.adamw(1e-3), mesh)

    with jax.default_device(jax.local_devices()[0]):
        params = gpt_init(jax.random.PRNGKey(0), cfg)  # same seed every rank
        state = init_fn(params)
        rng = np.random.default_rng(0)  # same batch every rank
        batch_host = rng.integers(
            0, cfg.vocab_size, size=(n_global // tp * 2, cfg.seq_len + 1)
        ).astype(np.int32)
        batch = global_put(batch_host, NamedSharding(mesh, batch_spec()))
        losses = []
        for _ in range(args.steps):
            state, loss = step_fn(state, batch)
            losses.append(float(loss))  # replicated scalar: addressable everywhere
    assert all(np.isfinite(l) and l > 0 for l in losses), losses
    assert losses[-1] < losses[0], f"loss did not decrease: {losses}"
    print(f"MSPROC rank={args.rank} losses={losses}", flush=True)
    jax.distributed.shutdown()


if __name__ == "__main__":
    main()
