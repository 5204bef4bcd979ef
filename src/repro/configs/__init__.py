"""Architecture configs: one module per assigned architecture + the paper's
own IVIM config. ``registry.get_config(arch_id)`` returns the exact public
config; ``registry.smoke_config(arch_id)`` a reduced same-family variant for
CPU smoke tests. ``cells.py`` enumerates the (arch x shape) dry-run cells
with documented skips."""

from repro.configs.base import InputShape, ModelConfig, SHAPES  # noqa: F401
from repro.configs.registry import (  # noqa: F401
    ARCH_IDS, get_config, smoke_config)
