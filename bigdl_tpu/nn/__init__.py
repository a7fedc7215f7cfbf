"""bigdl_tpu.nn — the layer zoo (parity inventory: SURVEY.md §2.4)."""

from bigdl_tpu.core.module import (
    Module, Container, Sequential, Identity, Lambda,
)
from bigdl_tpu.nn.activation import *  # noqa: F401,F403
from bigdl_tpu.nn.linear import *  # noqa: F401,F403
from bigdl_tpu.nn.conv import *  # noqa: F401,F403
from bigdl_tpu.nn.pool import *  # noqa: F401,F403
from bigdl_tpu.nn.norm import *  # noqa: F401,F403
from bigdl_tpu.nn.structural import *  # noqa: F401,F403
from bigdl_tpu.nn.recurrent import *  # noqa: F401,F403
from bigdl_tpu.nn.attention import *  # noqa: F401,F403
from bigdl_tpu.nn.ssm import *  # noqa: F401,F403
from bigdl_tpu.nn.linear_attention import *  # noqa: F401,F403
from bigdl_tpu.nn.moe import *  # noqa: F401,F403
from bigdl_tpu.nn.criterion import *  # noqa: F401,F403
