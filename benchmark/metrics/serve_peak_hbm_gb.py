"""Peak device memory on the fullest chip: the largest single reading of
live arrays + program scratch that `harness.MemorySampler` took while the
window ran."""
from benchmark.metrics._common import peak_hbm_gb as read  # noqa: F401
