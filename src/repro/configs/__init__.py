"""Architecture registry: one module per assigned architecture.

``get_config(arch_id, **overrides)`` returns the full published config;
``get_smoke_config(arch_id)`` returns a reduced same-family config for CPU
tests.
"""
from .registry import (
    ARCHS, RESNET_ARCHS, get_config, get_resnet, get_smoke_config,
)
