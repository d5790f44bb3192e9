"""Op kernel library. Importing this package registers all kernels.

Reference: paddle/operators/ — 191 op families registered via REGISTER_OP
(framework/op_registry.h:148). Here each submodule registers pure-JAX
kernels with core.registry; gradients are derived by jax.grad over the
traced program instead of hand-written grad kernels.
"""

from . import activation_ops  # noqa: F401
from . import attention_ops  # noqa: F401
from . import control_flow_ops  # noqa: F401
from . import cost_ops  # noqa: F401
from . import crf_ops  # noqa: F401
from . import misc_ops  # noqa: F401
from . import moe_ops  # noqa: F401
from . import ctc_ops  # noqa: F401
from . import detection_ops  # noqa: F401
from . import generation_ops  # noqa: F401
from . import math_ops  # noqa: F401
from . import nn_ops  # noqa: F401
from . import flash_ops  # noqa: F401
from . import sparse_attention_ops  # noqa: F401
from . import fused_conv_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import quant_kernels  # noqa: F401
from . import recurrent_ops  # noqa: F401
from . import rnn_ops  # noqa: F401
from . import sequence_ops  # noqa: F401
from . import ssm_ops  # noqa: F401
from . import short_conv_ops  # noqa: F401
from ..core.registry import registered_ops  # noqa: F401
