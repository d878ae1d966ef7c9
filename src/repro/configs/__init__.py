"""Architecture configs, by ``--arch`` name."""

from .base import (  # noqa: F401
    EncoderConfig,
    FrontendConfig,
    MLAConfig,
    ModelConfig,
    MoEConfig,
    RNNConfig,
    SSMConfig,
)
from .registry import (  # noqa: F401
    ARCHS,
    get_config,
    get_smoke_config,
)
