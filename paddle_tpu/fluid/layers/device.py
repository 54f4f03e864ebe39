"""Device placement helper (ref: python/paddle/fluid/layers/device.py).

``get_places`` is deprecated in the reference in favour of
ParallelExecutor; here the TPU-native replacement is CompiledProgram /
pjit over a Mesh, so this returns the host-visible device list for
introspection and keeps old scripts importable.
"""
from .. import core
from ..framework import cpu_places, tpu_places

__all__ = []


def get_places(device_count=None, device_type=None):
    """Return up to ``device_count`` Places of ``device_type``
    ('CPU'/'TPU', default: the kind jax's backend exposes); deprecated — use CompiledProgram.with_data_parallel,
    which shards over the full jax mesh (ref layers/device.py:30)."""
    if device_type is None:
        device_type = "TPU" if core._default_backend() == "tpu" else "CPU"
    dt = str(device_type).upper()
    if dt == "TPU":
        places = tpu_places()
    elif dt == "CPU":
        places = cpu_places()
    else:
        raise ValueError(
            "get_places supports device_type 'CPU' or 'TPU' on this "
            "build, got %r (CUDA scripts: the TPU devices replace GPUs)."
            % device_type)
    # ref semantics: device_count 0/None means every available device
    if device_count:
        places = places[: int(device_count)]
    return places
