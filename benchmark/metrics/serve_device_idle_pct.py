"""Share of the traced window in which no operation ran on the device (the
fullest chip where there are several): 1 - union of the device-op
intervals over the window."""
from benchmark.metrics._common import device_idle_pct as read  # noqa: F401
