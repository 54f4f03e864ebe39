"""Op lowering library — importing this package registers all lowerings."""
from .registry import (  # noqa: F401
    LOWERINGS,
    LowerContext,
    get_lowering,
    has_lowering,
    register_op,
)

from . import math_ops  # noqa: F401
from . import tensor_ops  # noqa: F401
from . import nn_ops  # noqa: F401
from . import loss_ops  # noqa: F401
from . import random_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import metric_ops  # noqa: F401
from . import misc_ops  # noqa: F401
from . import sequence_ops  # noqa: F401
from . import control_ops  # noqa: F401
from . import collective_ops  # noqa: F401
from . import pallas_attention  # noqa: F401
from . import detection_ops  # noqa: F401
from . import rcnn_ops  # noqa: F401
from . import rnn_ops  # noqa: F401
from . import crf_ops  # noqa: F401
from . import hybrid_ops  # noqa: F401
from . import vision_ops  # noqa: F401


def _register_late_modules():
    """All op modules are imported eagerly above; kept for compatibility."""
