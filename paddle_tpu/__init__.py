"""paddle_tpu — a TPU-native deep-learning framework with the capabilities
of PaddlePaddle (reference joegana/Paddle, surveyed in /root/repo/SURVEY.md),
re-designed for JAX/XLA:

* Program/Block/Op IR built by a fluid-style layers API,
* whole-block lowering to ONE jitted XLA computation per Executor.run
  (replacing the reference's per-op kernel interpreter),
* IR-level autodiff linked by jax.vjp at trace time,
* padded-sequence + lax.scan machinery replacing LoD,
* SPMD data/model parallelism over jax.sharding meshes replacing the
  pserver tier and NCCL ops.
"""

import time as _time

_T_IMPORT = _time.perf_counter()    # this import, top to bottom

from .core.framework import (  # noqa: F401
    Program, Variable, Parameter, default_main_program,
    default_startup_program, program_guard, name_scope)
from .core.scope import Scope, global_scope, scope_guard  # noqa: F401
from .core.executor import Executor  # noqa: F401
from .core.backward import append_backward  # noqa: F401
from .core import unique_name  # noqa: F401

from . import ops  # noqa: F401  (registers the op library)
from . import layers  # noqa: F401
from . import initializer  # noqa: F401
from . import optimizer  # noqa: F401
from . import regularizer  # noqa: F401
from . import clip  # noqa: F401
from . import nets  # noqa: F401
from . import config  # noqa: F401
from . import io  # noqa: F401
from . import reader  # noqa: F401
from . import evaluator  # noqa: F401
from . import lr_scheduler  # noqa: F401
from . import utils  # noqa: F401
from . import observability  # noqa: F401
from . import inference  # noqa: F401
from . import serving  # noqa: F401
from . import resilience  # noqa: F401
from . import distributed  # noqa: F401  (paddle_elastic_* always-on)
from . import embeddings  # noqa: F401  (registers lookup_table_dist ops)
from .data_feeder import DataFeeder  # noqa: F401
from .param_attr import ParamAttr  # noqa: F401
from .place import CPUPlace, TPUPlace, CUDAPlace, is_compiled_with_tpu  # noqa: F401

__version__ = "0.1.0"

observability.metrics.REGISTRY.gauge(
    "paddle_process_import_seconds",
    "Seconds the import of paddle_tpu took in this process, top to bottom "
    "(jax and the op library included when nothing imported them before)"
).set(_time.perf_counter() - _T_IMPORT)
