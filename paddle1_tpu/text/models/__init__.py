"""Model zoo for paddle1_tpu.text (flagship transformer configs)."""

from .bert import (BertForPretraining, BertForSequenceClassification,
                   BertModel, BertPretrainingCriterion, ErnieForPretraining,
                   ErnieModel, apply_megatron_sharding, bert_base, bert_large,
                   ernie_1p5b)
from .kanana2 import (Kanana2DecoderLayer, Kanana2ForPretraining,
                      Kanana2Head, Kanana2PretrainingCriterion, Kanana2Stack,
                      LatentAttention)
from .laguna import (LagunaAttention, LagunaDecoderLayer,
                     LagunaForPretraining, LagunaPretrainingCriterion)
from .lfm2 import (Lfm2Attention, Lfm2DecoderLayer, Lfm2ForPretraining,
                   Lfm2Head, Lfm2PretrainingCriterion, Lfm2Stack)
from .nemotron_h import (Mamba2Mixer, NemotronHForPretraining,
                         NemotronHLayer, NemotronHPretrainingCriterion)
from .ouro import (OuroDecoderLayer, OuroExitHead, OuroForPretraining,
                   OuroPretrainingCriterion, OuroStack)
from .sdar import (SdarAttention, SdarBlockDiffusionCriterion,
                   SdarDecoderLayer, SdarForBlockDiffusion, SdarStack)
from .smallthinker import (SmallThinkerAttention, SmallThinkerDecoderLayer,
                           SmallThinkerForPretraining,
                           SmallThinkerPretrainingCriterion)

__all__ = ["BertModel", "BertForPretraining", "BertPretrainingCriterion",
           "BertForSequenceClassification", "ErnieModel",
           "ErnieForPretraining", "apply_megatron_sharding", "bert_base",
           "bert_large", "ernie_1p5b", "OuroDecoderLayer", "OuroStack",
           "OuroExitHead", "OuroForPretraining", "OuroPretrainingCriterion",
           "LatentAttention", "Kanana2DecoderLayer", "Kanana2Stack",
           "Kanana2Head", "Kanana2ForPretraining",
           "Kanana2PretrainingCriterion", "SdarAttention",
           "SdarDecoderLayer", "SdarStack", "SdarForBlockDiffusion",
           "SdarBlockDiffusionCriterion", "Lfm2Attention",
           "Lfm2DecoderLayer", "Lfm2Stack", "Lfm2Head", "Lfm2ForPretraining",
           "Lfm2PretrainingCriterion", "SmallThinkerAttention",
           "SmallThinkerDecoderLayer", "SmallThinkerForPretraining",
           "SmallThinkerPretrainingCriterion", "LagunaAttention",
           "LagunaDecoderLayer", "LagunaForPretraining",
           "LagunaPretrainingCriterion", "Mamba2Mixer", "NemotronHLayer",
           "NemotronHForPretraining", "NemotronHPretrainingCriterion"]
