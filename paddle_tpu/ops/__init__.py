"""Operator library.

Reference parity: /root/reference/paddle/fluid/operators/ (~460 op types).
Each module registers pure-JAX compute functions with the registry
(paddle_tpu/core/registry.py); kernels, shape inference and gradients all
derive from the one function.
"""

from paddle_tpu.ops import basic  # noqa: F401
from paddle_tpu.ops import nn  # noqa: F401
from paddle_tpu.ops import optim  # noqa: F401
from paddle_tpu.ops import metrics  # noqa: F401
from paddle_tpu.ops import control_flow  # noqa: F401
from paddle_tpu.ops import sequence  # noqa: F401
from paddle_tpu.ops import collective  # noqa: F401
from paddle_tpu.ops import io_ops  # noqa: F401
from paddle_tpu.ops import detection  # noqa: F401
from paddle_tpu.ops import amp  # noqa: F401
from paddle_tpu.ops import parallel_ops  # noqa: F401
from paddle_tpu.ops import quant  # noqa: F401
from paddle_tpu.ops import pallas_kernels  # noqa: F401
from paddle_tpu.ops import pallas_conv  # noqa: F401
from paddle_tpu.ops import epilogue  # noqa: F401
from paddle_tpu.ops import ps_ops  # noqa: F401
from paddle_tpu.ops import loss_ops  # noqa: F401
from paddle_tpu.ops import vision  # noqa: F401
from paddle_tpu.ops import misc  # noqa: F401
from paddle_tpu.ops import rnn_ops  # noqa: F401
from paddle_tpu.ops import fused_ops  # noqa: F401
from paddle_tpu.ops import llm_ops  # noqa: F401
from paddle_tpu.ops import ssd_ops  # noqa: F401
from paddle_tpu.ops import kda_ops  # noqa: F401
from paddle_tpu.ops import eva_ops  # noqa: F401
