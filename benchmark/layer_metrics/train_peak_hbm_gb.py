"""``peak_hbm_gb`` of a training cell (see ``train_device_idle_share``)."""

from peak_hbm_gb import read  # noqa: F401
