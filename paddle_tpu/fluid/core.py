"""Device places and dtype plumbing.

TPU-native analogue of the reference's ``paddle/fluid/platform/place.h`` and
``fluid.core`` pybind surface (ref: python/paddle/fluid/core.py). Instead of a
CUDAPlace/CPUPlace dispatch into per-op kernels, a Place here selects the JAX
backend the lowered XLA module is compiled for.
"""
import os

import numpy as np


class Place:
    """Base device placement."""

    _backend = "cpu"
    _device_id = 0

    def __init__(self, device_id=0):
        self._device_id = int(device_id)

    def jax_device(self):
        """The jax device this place names. Raises when the backend has
        no such device: a TPUPlace never lands on the CPU, and an id
        past the last device never wraps onto another one."""
        import jax

        if self._backend == "cpu":
            devs = jax.devices("cpu")
        else:
            devs = [d for d in jax.devices() if d.platform != "cpu"]
            if not devs:
                raise RuntimeError(
                    "%r: jax found no accelerator (backend %r, devices %s)"
                    " — use CPUPlace, or fluid.Executor() whose default "
                    "place follows the backend"
                    % (self, jax.default_backend(), jax.devices()))
        if not 0 <= self._device_id < len(devs):
            raise RuntimeError(
                "%r: device id out of range, the backend has %d %s "
                "device(s)" % (self, len(devs), devs[0].platform))
        return devs[self._device_id]

    def __eq__(self, other):
        return (
            type(self) is type(other) and self._device_id == other._device_id
        )

    def __hash__(self):
        return hash((type(self).__name__, self._device_id))

    def __repr__(self):
        return "%s(%d)" % (type(self).__name__, self._device_id)


class CPUPlace(Place):
    _backend = "cpu"


class TPUPlace(Place):
    """First-class TPU placement — the analogue of the reference CUDAPlace."""

    _backend = "tpu"


class CUDAPlace(TPUPlace):
    """Compatibility alias: reference code that asks for CUDAPlace gets the
    accelerator backend (TPU) so existing scripts run unmodified."""


class CUDAPinnedPlace(CPUPlace):
    pass


def _default_backend():
    """"tpu" when jax's default backend exposes any accelerator, else
    "cpu" (JAX_PLATFORMS=cpu makes that choice from outside). A backend
    that fails to initialise raises: it is never read as "cpu"."""
    import jax

    if {d.platform for d in jax.devices()} - {"cpu"}:
        return "tpu"
    return "cpu"


def default_place():
    if _default_backend() == "tpu":
        return TPUPlace(0)
    return CPUPlace(0)


def is_compiled_with_cuda():
    # The accelerator path here is TPU; report False like a CPU/TPU build.
    return False


def is_compiled_with_tpu():
    return True


class VarType:
    """dtype + variable-kind enums, mirroring VarDesc.VarType in
    framework.proto (ref: paddle/fluid/framework/framework.proto)."""

    # dtypes
    BOOL = "bool"
    INT8 = "int8"
    UINT8 = "uint8"
    INT16 = "int16"
    INT32 = "int32"
    INT64 = "int64"
    FP16 = "float16"
    BF16 = "bfloat16"
    FP32 = "float32"
    FP64 = "float64"
    # var kinds
    LOD_TENSOR = "lod_tensor"
    SELECTED_ROWS = "selected_rows"
    FEED_MINIBATCH = "feed_minibatch"
    FETCH_LIST = "fetch_list"
    STEP_SCOPES = "step_scopes"
    LOD_TENSOR_ARRAY = "lod_tensor_array"
    RAW = "raw"


class VarDesc:
    VarType = VarType


_NP_TO_STR = {
    np.dtype("bool"): VarType.BOOL,
    np.dtype("int8"): VarType.INT8,
    np.dtype("uint8"): VarType.UINT8,
    np.dtype("int16"): VarType.INT16,
    np.dtype("int32"): VarType.INT32,
    np.dtype("int64"): VarType.INT64,
    np.dtype("float16"): VarType.FP16,
    np.dtype("float32"): VarType.FP32,
    np.dtype("float64"): VarType.FP64,
}


def convert_dtype(dtype):
    """Normalise any dtype spec (np dtype, str, jnp dtype) to a canonical
    string like 'float32'."""
    if dtype is None:
        return VarType.FP32
    if isinstance(dtype, str):
        aliases = {
            "float": "float32",
            "double": "float64",
            "int": "int32",
            "long": "int64",
            "half": "float16",
            "bfloat16": "bfloat16",
        }
        return aliases.get(dtype, dtype)
    try:
        import jax.numpy as jnp

        if dtype in (jnp.bfloat16,):
            return VarType.BF16
    except Exception:
        pass
    return _NP_TO_STR.get(np.dtype(dtype), str(np.dtype(dtype)))


def np_dtype(dtype_str):
    import jax.numpy as jnp

    if dtype_str == VarType.BF16:
        return jnp.bfloat16
    return np.dtype(dtype_str)


def globals_flags():
    return dict(os.environ)


class EOFException(Exception):
    """Raised by Executor.run when an attached py_reader is exhausted
    (ref: paddle/fluid/framework/reader.h EOFException) — catch it to end
    the epoch, then reader.reset()."""


class ReaderNotStartedError(RuntimeError):
    """Raised by Executor.run when no feed was given and the program's
    py_reader is decorated but not started (or went EOF without a
    reset()+start()). A config error, not a transient — never retried
    by resilience.GuardedExecutor."""


def __getattr__(name):
    # deployment scripts reach AnalysisConfig / create_paddle_predictor
    # through fluid.core (the reference exposes them via pybind); lazy to
    # avoid a core <-> inference import cycle
    if name in ("AnalysisConfig", "create_paddle_predictor"):
        from . import inference

        return getattr(inference, name)
    raise AttributeError("module 'core' has no attribute %r" % name)
