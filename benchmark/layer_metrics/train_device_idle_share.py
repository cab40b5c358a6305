"""``device_idle_share`` of a training cell.  A per-layer metric names the
ONE end-to-end metric it moves, and a training cell's is not a serving
cell's, so the same reading is listed once for each."""

from device_idle_share import read  # noqa: F401
