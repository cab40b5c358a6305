"""TPU topology detection and resource synthesis.

Counterpart of the reference's ``python/ray/_private/accelerators/tpu.py``
(GKE/GCE metadata probing :14-28, ``TPU_VISIBLE_CHIPS`` :30, pod detection,
``TPU-{version}-{pod}-head`` resource synthesis) — but TPU-first: here the
chip is the *primary* accelerator, and slice topology (hosts × chips, ICI
domain) is what placement groups reserve.

Detection never touches jax: a chip belongs to ONE process at a time, and a
driver that asked jax for its devices would own the chip its workers need.
It probes, in order: ``RAY_TPU_CHIPS`` env, ``TPU_VISIBLE_CHIPS``/
``TPU_CHIPS_PER_HOST``, GCE metadata env mirrors (``TPU_ACCELERATOR_TYPE``)
bounded by the chip device nodes this host actually exposes, and finally
the ``/dev/accel<N>`` nodes alone.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Optional

# chips per host for each accelerator generation (v4/v5p: 4 chips/host;
# v5e/v6e: up to 8)
_CHIPS_PER_HOST = {"v2": 4, "v3": 4, "v4": 4, "v5p": 4, "v5litepod": 8, "v5e": 8, "v6e": 8}


def accelerator_type() -> Optional[str]:
    """e.g. 'v5litepod-256' / 'v5e-8' from env (GCE metadata mirror)."""
    for var in ("TPU_ACCELERATOR_TYPE", "RAY_TPU_ACCELERATOR_TYPE"):
        v = os.environ.get(var)
        if v:
            return v
    return None


def parse_accelerator_type(acc: str) -> tuple[str, int]:
    """'v5litepod-256' -> ('v5litepod', 256 chips in the pod slice)."""
    m = re.match(r"(v\d+[a-z]*)-(\d+)", acc)
    if not m:
        raise ValueError(f"Unrecognized TPU accelerator type {acc!r}")
    return m.group(1), int(m.group(2))


def _chip_device_nodes(vfio: bool) -> int:
    """Chip device nodes visible to this host: ``/dev/accel<N>`` (through
    v4).  v5e and later expose numbered ``/dev/vfio`` groups instead —
    as does any PCI device passed through to a VM, so those count only
    where ``vfio`` says the host is known to be a TPU host."""
    nodes = len(glob.glob("/dev/accel[0-9]*"))
    if not nodes and vfio:
        nodes = len(glob.glob("/dev/vfio/[0-9]*"))
    return nodes


def detect_num_chips() -> int:
    """Number of TPU chips attached to *this host*."""
    env = os.environ.get("RAY_TPU_CHIPS") or os.environ.get("TPU_CHIPS_PER_HOST")
    if env:
        return int(env)
    visible = os.environ.get("TPU_VISIBLE_CHIPS")
    if visible:
        return len([c for c in visible.split(",") if c.strip()])
    acc = accelerator_type()
    if acc:
        gen, pod_chips = parse_accelerator_type(acc)
        declared = min(pod_chips, _CHIPS_PER_HOST.get(gen, 4))
        # a host can be handed fewer chips than its slice type names
        nodes = _chip_device_nodes(vfio=True)
        return min(declared, nodes) if nodes else declared
    return _chip_device_nodes(vfio=False)


def extra_resources(num_chips: int) -> dict[str, float]:
    """Synthesized resources for slice-aware scheduling, mirroring the
    reference's ``TPU-{version}-{pod}-head`` trick: the first host of a pod
    slice exposes a head resource so exactly one actor can claim slice
    leadership, and every host exposes an accelerator-type resource for
    affinity."""
    out: dict[str, float] = {}
    acc = accelerator_type()
    if acc:
        out[f"TPU-{acc}"] = float(num_chips)
        worker_id = int(os.environ.get("TPU_WORKER_ID", "0") or 0)
        if worker_id == 0:
            out[f"TPU-{acc}-head"] = 1.0
    return out


def slice_hosts(acc: str) -> int:
    """Hosts in a slice of the given accelerator type."""
    gen, pod_chips = parse_accelerator_type(acc)
    per_host = _CHIPS_PER_HOST.get(gen, 4)
    return max(1, pod_chips // per_host)
