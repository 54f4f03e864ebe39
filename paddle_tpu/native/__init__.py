"""Native (C++) host runtime: prefetching data pipeline + pinned staging
arena (TPU-native analogue of paddle/fluid/operators/reader/ +
paddle/fluid/memory/). Built lazily with g++ (native/build.py); the
pure-python queue serves only where there is no g++ to build with."""
from . import pipeline  # noqa: F401
