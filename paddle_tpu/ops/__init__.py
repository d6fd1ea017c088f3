"""Operator library: JAX lowerings for the Fluid op set.

Importing this package registers every op. Organization mirrors the
reference's operator directories (reference: paddle/fluid/operators/) but each
"kernel" is an XLA-traceable lowering, not a CPU/CUDA functor — see
paddle_tpu/core/registry.py for the registration model.
"""

from paddle_tpu.ops import math_ops  # noqa: F401
from paddle_tpu.ops import activation_ops  # noqa: F401
from paddle_tpu.ops import tensor_ops  # noqa: F401
from paddle_tpu.ops import nn_ops  # noqa: F401
from paddle_tpu.ops import loss_ops  # noqa: F401
from paddle_tpu.ops import reduce_ops  # noqa: F401
from paddle_tpu.ops import optimizer_ops  # noqa: F401
from paddle_tpu.ops import metric_ops  # noqa: F401
from paddle_tpu.ops import sequence_ops  # noqa: F401
from paddle_tpu.ops import controlflow_ops  # noqa: F401
from paddle_tpu.ops import quant_ops  # noqa: F401
from paddle_tpu.ops import rnn_ops  # noqa: F401
from paddle_tpu.ops import beam_search_ops  # noqa: F401
from paddle_tpu.ops import distributed_ops  # noqa: F401
from paddle_tpu.ops import detection_ops  # noqa: F401
from paddle_tpu.ops import misc_ops  # noqa: F401
from paddle_tpu.ops import moe_ops  # noqa: F401
