"""Flagship model families (reference role: the hapi/vision zoo's
NLP-side counterpart): Llama decoder (pretraining flagship) and BERT
encoder."""
from paddle_tpu.models import bert, llama  # noqa: F401
from paddle_tpu.models.bert import (  # noqa: F401
    BertConfig, BertForMaskedLM, BertForSequenceClassification, BertModel,
    BertPretrainingCriterion,
)
from paddle_tpu.models.llama import (  # noqa: F401
    LlamaConfig, LlamaForCausalLM, LlamaPretrainingCriterion,
)
