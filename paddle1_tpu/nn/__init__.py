"""paddle1_tpu.nn — layer library (reference python/paddle/nn analog)."""

from . import functional
from . import initializer
from .clip import (ClipGradByGlobalNorm, ClipGradByNorm, ClipGradByValue,
                   clip_grad_norm_, clip_grad_value_)
from .layer_base import Layer
from .layer_common import *  # noqa: F401,F403
from .layer_conv_pool import *  # noqa: F401,F403
from .layer_loss import *  # noqa: F401,F403
from .layer_norm_act import *  # noqa: F401,F403
from .layer_rnn import *  # noqa: F401,F403
from .decode import *  # noqa: F401,F403
from .layer_transformer import *  # noqa: F401,F403
from .layer_moe import *  # noqa: F401,F403
from .tiered_embedding import TieredEmbedding  # noqa: F401
from ..framework.param_attr import ParamAttr  # re-export convenience
