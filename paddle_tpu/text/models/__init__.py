from .bert import (  # noqa: F401
    BERT_BASE, BERT_LARGE, BERT_TINY, BertConfig, BertForPretraining,
    BertForSequenceClassification, BertModel,
)
from .ernie_moe import (  # noqa: F401
    ERNIE_MOE_TINY, ErnieMoEConfig, ErnieMoEForPretraining, ErnieMoEModel,
)
from .gpt import GPT_TINY, GPTConfig, GPTForCausalLM, GPTModel  # noqa: F401
from .kimi_k2 import (  # noqa: F401
    KIMI_K2_TINY, KimiK2Config, KimiK2ForCausalLM,
)
from .llama import (  # noqa: F401
    LLAMA2_7B, LLAMA2_13B, LLAMA_TINY, LlamaConfig, LlamaForCausalLM,
    LlamaModel,
)
from .llama_pipe import LlamaForCausalLMPipe  # noqa: F401
from .mellum import (  # noqa: F401
    MELLUM_TINY, MellumConfig, MellumForCausalLM,
)
from .phi4flash import (  # noqa: F401
    PHI4FLASH_TINY, Phi4FlashConfig, Phi4FlashForCausalLM,
)
from .t5 import (  # noqa: F401
    T5_TINY, T5Config, T5ForConditionalGeneration, T5Model,
)
from . import convert  # noqa: F401
