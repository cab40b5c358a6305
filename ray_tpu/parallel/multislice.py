"""Multi-slice meshes: data parallelism over DCN, everything else on ICI.

SURVEY §7 names 8→256-chip scaling via DCN-overlapped gradient reduction as
make-or-break. The reference scales across hosts with NCCL rings over the
datacenter network; the TPU-native design is a HYBRID device mesh
(reference mental model: the scaling-book's multi-slice recipe, and jax's
``mesh_utils.create_hybrid_device_mesh``):

* within a slice, devices are ordered so tp/sp/fsdp collectives ride
  adjacent ICI links (same nesting as ``parallel.mesh.AXES``);
* the ``dp`` axis is SLICE-MAJOR: its groups pair corresponding chips of
  different slices, so data-parallel gradient reduction is the only
  traffic that crosses DCN.

No new axis name is introduced — the model/sharding code is unchanged.
GSPMD decomposes the dp all-reduce hierarchically over the hybrid ordering
(reduce-scatter on ICI → cross-slice exchange on DCN → all-gather on ICI),
and XLA's latency-hiding scheduler overlaps the DCN phase with ICI compute
of neighbouring layers — the overlap SURVEY §7 asks for comes from the
compiler, not hand-written schedules.

Real multi-slice pods are detected through ``device.slice_index`` (set by
the TPU runtime); anywhere else (CPU dryruns, single slice) the devices are
partitioned into ``num_slices`` contiguous groups, which preserves the
slice-major dp semantics for compile-and-execute validation on a virtual
mesh.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
from typing import Optional, Sequence

import numpy as np

from ray_tpu.parallel.mesh import AXES, MeshConfig


def slice_groups(devices: Sequence, num_slices: Optional[int] = None) -> list[list]:
    """Partition devices into slices: by the runtime's ``slice_index`` when
    present, else into ``num_slices`` contiguous groups."""
    by_idx: dict[int, list] = {}
    if all(getattr(d, "slice_index", None) is not None for d in devices):
        for d in devices:
            by_idx.setdefault(d.slice_index, []).append(d)
        groups = [by_idx[i] for i in sorted(by_idx)]
        if num_slices is None or len(groups) == num_slices:
            return groups
        if len(groups) > 1:
            # asking to re-partition across REAL slice boundaries would put
            # ICI axes over DCN — reject; simulation is only meaningful on
            # a single physical slice (or CPU)
            raise ValueError(
                f"hardware reports {len(groups)} slices, requested {num_slices}"
            )
        # single physical slice + explicit num_slices: fall through to the
        # simulated contiguous partitioning (compile-and-execute validation)
    if num_slices is None:
        return [list(devices)]
    if num_slices <= 0:
        raise ValueError(f"num_slices must be positive, got {num_slices}")
    n = len(devices)
    if n % num_slices:
        raise ValueError(f"{n} devices not divisible into {num_slices} slices")
    per = n // num_slices
    return [list(devices[i * per : (i + 1) * per]) for i in range(num_slices)]


def make_multislice_mesh(
    config: Optional[MeshConfig] = None,
    *,
    num_slices: Optional[int] = None,
    devices: Optional[Sequence] = None,
    axis_names: Sequence[str] = AXES,
):
    """Build a hybrid mesh whose dp axis crosses slices (DCN) while the
    remaining axes stay within a slice (ICI).

    ``config`` sizes are TOTALS (like ``make_mesh``); dp must be a multiple
    of the slice count — each slice contributes ``dp // num_slices`` local
    dp groups, and dp's MAJOR dimension enumerates slices.
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    groups = slice_groups(devices, num_slices)
    s = len(groups)
    config = config or MeshConfig()
    sizes = config.resolve(len(devices))
    if sizes["dp"] % s:
        raise ValueError(
            f"dp={sizes['dp']} must be a multiple of the slice count {s} "
            f"(data parallelism is the axis that crosses DCN)"
        )
    non_dp = [a for a in axis_names if a != "dp"]
    per_slice_shape = [sizes["dp"] // s] + [sizes[a] for a in non_dp]
    # (slice, dp_local, rest...) → merge (slice, dp_local) into slice-major dp
    arr = np.stack(
        [np.asarray(g).reshape(per_slice_shape) for g in groups], axis=0
    ).reshape([sizes["dp"]] + per_slice_shape[1:])
    # restore the caller's axis order (dp first in AXES already)
    order = ["dp"] + non_dp
    perm = [order.index(a) for a in axis_names]
    arr = np.transpose(arr, perm)
    return Mesh(arr, axis_names=tuple(axis_names))


def launch_multislice_procs(
    num_procs: int = 2,
    local_devices: int = 4,
    steps: int = 2,
    timeout: float = 600.0,
) -> list[list[float]]:
    """Run the REAL multi-process multislice dryrun: ``num_procs`` fresh
    subprocesses, each ``jax.distributed.initialize``-ing into one shared
    runtime with ``local_devices`` virtual CPU chips, training the tiny GPT
    over a single global mesh whose dp axis crosses the process boundary
    (``_multislice_worker.py``; reference counterpart: the cross-host torch
    process group in ``python/ray/train/torch/config.py:47-91``).

    Returns per-rank loss trajectories (all ranks must agree bit-for-bit:
    the update is a deterministic function of replicated inputs, so
    agreement proves the cross-process collective ran correctly).
    """
    # the free-port probe is TOCTOU (another process can claim it between
    # close and the coordinator's bind): retry the whole launch on a fresh
    # port when the failure smells like a bind clash
    last_err: Optional[BaseException] = None
    for _attempt in range(3):
        try:
            return _launch_once(num_procs, local_devices, steps, timeout)
        except RuntimeError as e:
            msg = str(e).lower()
            if "bind" in msg or "address" in msg or "in use" in msg:
                last_err = e
                continue
            raise
    raise last_err  # type: ignore[misc]


def _launch_once(
    num_procs: int, local_devices: int, steps: int, timeout: float
) -> list[list[float]]:
    import tempfile
    import time as _time

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"

    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")

    # output to files, not pipes: a crashed rank's log must survive the
    # kill path, and pipes deadlock if a worker fills one while we block
    # on a sibling's communicate()
    logs = [tempfile.NamedTemporaryFile("w+", suffix=f".ms{r}.log") for r in range(num_procs)]
    procs = [
        subprocess.Popen(
            [
                sys.executable, "-m", "ray_tpu.parallel._multislice_worker",
                "--rank", str(r), "--coord", coord,
                "--procs", str(num_procs),
                "--local-devices", str(local_devices),
                "--steps", str(steps),
            ],
            env=env,
            stdout=logs[r],
            stderr=subprocess.STDOUT,
            text=True,
        )
        for r in range(num_procs)
    ]

    def read_log(r: int) -> str:
        logs[r].flush()
        logs[r].seek(0)
        return logs[r].read()

    try:
        deadline = _time.monotonic() + timeout
        while _time.monotonic() < deadline:
            rcs = [p.poll() for p in procs]
            # any rank dying early would leave the others waiting in
            # distributed barriers until the full timeout: fail fast with
            # the crashed rank's log (the informative one)
            if any(rc is not None and rc != 0 for rc in rcs):
                bad = next(r for r, rc in enumerate(rcs) if rc not in (None, 0))
                raise RuntimeError(
                    f"multislice worker rank {bad} failed "
                    f"(rc={rcs[bad]}):\n{read_log(bad)[-4000:]}"
                )
            if all(rc == 0 for rc in rcs):
                break
            _time.sleep(0.2)
        else:
            raise RuntimeError(
                "multislice dryrun timed out; rank logs:\n"
                + "\n---\n".join(read_log(r)[-2000:] for r in range(num_procs))
            )
        outs = [read_log(r) for r in range(num_procs)]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for f in logs:
            f.close()
    losses: list[list[float]] = [None] * num_procs  # type: ignore[list-item]
    for p, out in zip(procs, outs):
        for line in out.splitlines():
            if line.startswith("MSPROC rank="):
                rank = int(line.split("rank=")[1].split()[0])
                losses[rank] = eval(line.split("losses=")[1])  # noqa: S307 - our own output
    if any(l is None for l in losses):
        raise RuntimeError(f"missing MSPROC lines in worker output:\n{outs}")
    for r in range(1, num_procs):
        if losses[r] != losses[0]:
            raise RuntimeError(
                f"rank {r} diverged from rank 0: {losses[r]} vs {losses[0]} — "
                "cross-process collective inconsistency"
            )
    return losses
