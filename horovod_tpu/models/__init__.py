from .lenet import LeNet  # noqa: F401
from .resnet import ResNet, ResNet50, ResNet101, ResNet152  # noqa: F401
from .bert import (  # noqa: F401
    BERT_BASE,
    BERT_LARGE,
    BERT_TINY,
    Bert,
    BertConfig,
    mlm_loss,
)
from .olmoe import (  # noqa: F401
    OLMOE_1B_7B,
    OLMOE_TINY,
    Olmoe,
    OlmoeConfig,
    causal_lm_loss,
)
from .experts import routing_stats, take_expert_window  # noqa: F401
from .olmo_hybrid import (  # noqa: F401
    OLMO_HYBRID_7B,
    OLMO_HYBRID_TINY,
    OlmoHybrid,
    OlmoHybridConfig,
    take_head_window,
)
from .smallthinker import (  # noqa: F401
    SMALLTHINKER_21B_A3B,
    SMALLTHINKER_TINY,
    SmallThinker,
    SmallThinkerConfig,
)
from .sdar import (  # noqa: F401
    SDAR_30B_A3B,
    SDAR_TINY,
    Sdar,
    SdarConfig,
    block_diffusion_loss,
    noisy_batch,
)
from .granite import (  # noqa: F401
    GRANITE_4_0_H_MICRO,
    GRANITE_TINY,
    Granite,
    GraniteConfig,
)
from .kimi_linear import (  # noqa: F401
    KIMI_LINEAR_48B_A3B,
    KIMI_LINEAR_TINY,
    KimiLinear,
    KimiLinearConfig,
)
from .nemotron_h import (  # noqa: F401
    NEMOTRON_3_NANO_30B_A3B,
    NEMOTRON_H_TINY,
    NemotronH,
    NemotronHConfig,
)
from .joyai_flash import (  # noqa: F401
    JOYAI_FLASH_TINY,
    JOYAI_LLM_FLASH,
    JoyAIFlash,
    JoyAIFlashConfig,
    mtp_lm_loss,
)
from .lfm2 import (  # noqa: F401
    LFM2_24B_A2B,
    LFM2_TINY,
    Lfm2,
    Lfm2Config,
)
