"""paddle1_tpu.nn.functional — functional op namespace.

Analog of python/paddle/nn/functional/ in the reference.
"""

from .activation import *  # noqa: F401,F403
from .common import *  # noqa: F401,F403
from .conv import *  # noqa: F401,F403
from .loss import *  # noqa: F401,F403
from .norm import *  # noqa: F401,F403
from .pooling import *  # noqa: F401,F403
from .attention import (scaled_dot_product_attention, attention_ref,  # noqa: F401
                        paged_attention, rotary_embedding,
                        yarn_frequencies)
from .short_conv import gated_short_conv  # noqa: F401
from .ssd import ssd_scan  # noqa: F401
from .crf import crf_decoding, linear_chain_crf  # noqa: F401
