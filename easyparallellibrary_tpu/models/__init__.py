from easyparallellibrary_tpu.models.gpt import (
    GPT, GPTConfig, auto_parallel_gpt, make_gpt_train_step,
)
from easyparallellibrary_tpu.models.jamba import Jamba, JambaConfig
from easyparallellibrary_tpu.models.glm_moe import GlmMoe, GlmMoeConfig
from easyparallellibrary_tpu.models.lfm2_moe import Lfm2Moe, Lfm2MoeConfig
from easyparallellibrary_tpu.models.dots3_note import (
    Dots3Note, Dots3NoteConfig,
)
from easyparallellibrary_tpu.models.smallthinker import (
    SmallThinker, SmallThinkerConfig,
)
from easyparallellibrary_tpu.models.gigachat import (
    GigaChat, GigaChatConfig,
)
from easyparallellibrary_tpu.models.bert import (
    Bert, BertConfig, bert_large_config,
)
from easyparallellibrary_tpu.models.resnet import (
    ResNet, ResNetConfig, resnet18_config, resnet50_config,
)

__all__ = [
    "GPT", "GPTConfig", "auto_parallel_gpt", "make_gpt_train_step",
    "Jamba", "JambaConfig",
    "GlmMoe", "GlmMoeConfig",
    "Lfm2Moe", "Lfm2MoeConfig",
    "Dots3Note", "Dots3NoteConfig",
    "SmallThinker", "SmallThinkerConfig",
    "GigaChat", "GigaChatConfig",
    "Bert", "BertConfig", "bert_large_config",
    "ResNet", "ResNetConfig", "resnet18_config", "resnet50_config",
]
