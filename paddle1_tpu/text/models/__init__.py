"""Model zoo for paddle1_tpu.text (flagship transformer configs)."""

from .bert import (BertForPretraining, BertForSequenceClassification,
                   BertModel, BertPretrainingCriterion, ErnieForPretraining,
                   ErnieModel, apply_megatron_sharding, bert_base, bert_large,
                   ernie_1p5b)
from .ouro import (OuroDecoderLayer, OuroExitHead, OuroForPretraining,
                   OuroPretrainingCriterion, OuroStack)

__all__ = ["BertModel", "BertForPretraining", "BertPretrainingCriterion",
           "BertForSequenceClassification", "ErnieModel",
           "ErnieForPretraining", "apply_megatron_sharding", "bert_base",
           "bert_large", "ernie_1p5b", "OuroDecoderLayer", "OuroStack",
           "OuroExitHead", "OuroForPretraining", "OuroPretrainingCriterion"]
