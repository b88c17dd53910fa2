"""Flagship model zoo (reference: model fixtures used throughout the
reference's test and benchmark suites — GPT at
test/auto_parallel/get_gpt_model.py and
test/collective/fleet/hybrid_parallel_gpt fixtures; vision models live in
paddle_tpu.vision.models)."""
from . import generation, gpt  # noqa: F401
from .generation import GenerationMixin, KVCache  # noqa: F401
from .gpt import (  # noqa: F401
    GPTConfig,
    GPTModel,
    GPTForPretraining,
    GPTStackedDecoder,
    GPTStackedForPretraining,
    GPTPretrainingCriterion,
    gpt_tiny,
    gpt_small,
    gpt_1p3b,
    gpt_13b,
    truncated_draft,
)
from .lfm2 import (  # noqa: F401
    Lfm2Config, Lfm2StackedForCausalLM, lfm2_tiny,
)
from .phi4flash import (  # noqa: F401
    Phi4FlashConfig, Phi4FlashForCausalLM, phi4flash_tiny,
)
from .solar_open2 import (  # noqa: F401
    SolarOpen2Config, SolarOpen2ForCausalLM, solar_open2_tiny,
)
from .mellum2 import (  # noqa: F401
    Mellum2Config, Mellum2ForCausalLM, mellum2_tiny,
)
from .ernie_moe import (  # noqa: F401
    ErnieMoEConfig, ErnieMoEForPretraining, ErnieMoEModel, ernie_moe_tiny,
)
