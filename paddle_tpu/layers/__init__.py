from paddle_tpu.layers.helper import LayerHelper
from paddle_tpu.layers.nn import *  # noqa: F401,F403
from paddle_tpu.layers.tensor import *  # noqa: F401,F403
from paddle_tpu.layers.io import data, py_reader, read_file  # noqa: F401
from paddle_tpu.layers.control_flow import *  # noqa: F401,F403
from paddle_tpu.layers.learning_rate_scheduler import *  # noqa: F401,F403
from paddle_tpu.layers import sequence_ops  # noqa: F401
from paddle_tpu.layers.sequence_ops import *  # noqa: F401,F403
from paddle_tpu.layers import distributions  # noqa: F401
from paddle_tpu.layers.llm import *  # noqa: F401,F403
from paddle_tpu.layers.ssm import *  # noqa: F401,F403
from paddle_tpu.layers.kda import *  # noqa: F401,F403
from paddle_tpu.layers.eva import *  # noqa: F401,F403
from paddle_tpu.layers import detection  # noqa: F401
from paddle_tpu.layers.detection import *  # noqa: F401,F403
from paddle_tpu.layers.extras import (  # noqa: F401
    conv3d, conv3d_transpose, sequence_conv, row_conv,
    bilinear_tensor_product, gru_unit, lstm_unit, dynamic_lstmp, lstm,
    sync_batch_norm, spectral_norm, data_norm, deformable_conv,
    tree_conv, distribute_fpn_proposals)

# auto-generated single-op layers (reference layers/ops.py idiom via
# layer_function_generator.py:349) — fills every remaining op-without-
# layer gap without shadowing hand-written wrappers above
from paddle_tpu.layers import layer_function_generator as _lfg

_lfg.install(globals())
