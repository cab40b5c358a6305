"""Multi-slice hybrid meshes (parallel/multislice.py): dp crosses slice
(DCN) boundaries slice-major, every other axis stays within a slice (ICI).
Reference mental model: the multi-slice scaling recipe (SURVEY §7) /
jax mesh_utils.create_hybrid_device_mesh."""

import numpy as np
import pytest

from ray_tpu.parallel.mesh import MeshConfig
from ray_tpu.parallel.multislice import make_multislice_mesh, slice_groups


def _devices(n=8):
    import jax

    return jax.devices("cpu")[:n]


def test_slice_groups_contiguous():
    devs = _devices(8)
    groups = slice_groups(devs, 2)
    assert [len(g) for g in groups] == [4, 4]
    assert groups[0] == devs[:4] and groups[1] == devs[4:]
    with pytest.raises(ValueError, match="divisible"):
        slice_groups(devs[:6], 4)


def test_dp_axis_is_slice_major():
    devs = _devices(8)
    mesh = make_multislice_mesh(
        MeshConfig(dp=4, fsdp=1, tp=2, sp=1), num_slices=2, devices=devs
    )
    arr = np.asarray(mesh.devices)  # axes (dp, fsdp, ep, sp, tp)
    assert mesh.shape["dp"] == 4 and mesh.shape["tp"] == 2
    flat_dp = arr.reshape(4, 2)  # (dp, tp)
    # dp-major halves = slices: first two dp rows from slice 0, last two
    # from slice 1 — cross-slice traffic is dp-only
    slice_of = {d: 0 for d in devs[:4]} | {d: 1 for d in devs[4:]}
    dp_slices = [{slice_of[d] for d in row} for row in flat_dp]
    assert dp_slices == [{0}, {0}, {1}, {1}]
    # tp groups never cross a slice
    for row in flat_dp:
        assert len({slice_of[d] for d in row}) == 1


def test_dp_must_cover_slices():
    devs = _devices(8)
    with pytest.raises(ValueError, match="multiple of the slice count"):
        make_multislice_mesh(
            MeshConfig(dp=1, fsdp=1, tp=8, sp=1), num_slices=2, devices=devs
        )


def test_single_slice_degenerates_to_plain_mesh():
    devs = _devices(4)
    mesh = make_multislice_mesh(
        MeshConfig(dp=2, fsdp=1, tp=2, sp=1), num_slices=1, devices=devs
    )
    assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 2


def test_two_process_dcn_dp():
    """REAL multi-process multislice: 2 subprocesses jax.distributed-join
    one 8-device mesh; dp gradient reduction crosses the process boundary
    (gloo = the DCN stand-in); all ranks must agree bit-for-bit and the
    loss must decrease. Reference counterpart: the cross-host process group
    built by python/ray/train/torch/config.py:47-91."""
    from ray_tpu.parallel.multislice import launch_multislice_procs

    losses = launch_multislice_procs(num_procs=2, local_devices=4, steps=2)
    assert losses[0] == losses[1]
    assert losses[0][1] < losses[0][0]
