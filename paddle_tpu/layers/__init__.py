"""Layers API — flat namespace like reference ``fluid.layers``
(``python/paddle/v2/fluid/layers/``)."""

from .io import *        # noqa: F401,F403
from .nn import *        # noqa: F401,F403
from .tensor import *    # noqa: F401,F403
from .ops import *       # noqa: F401,F403
from .sequence import *  # noqa: F401,F403
from .control_flow import *  # noqa: F401,F403
from .detection import *  # noqa: F401,F403
from .beam_search import *  # noqa: F401,F403
from .legacy import *    # noqa: F401,F403
from .moe import *       # noqa: F401,F403
from .ssm import *       # noqa: F401,F403

from . import (io, nn, tensor, ops, sequence, control_flow, detection,  # noqa
               beam_search, legacy, moe, ssm)

__all__ = (io.__all__ + nn.__all__ + tensor.__all__ + ops.__all__ +
           sequence.__all__ + control_flow.__all__ + detection.__all__ +
           beam_search.__all__ + legacy.__all__ + moe.__all__ +
           ssm.__all__)
