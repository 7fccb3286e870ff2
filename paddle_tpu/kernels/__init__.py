"""Pallas TPU kernels (the hand-written hot ops; XLA handles the rest)."""
from .flash_attention import flash_attention, flash_attention_arrays  # noqa: F401
from .power_retention import (  # noqa: F401
    power_retention_chunked, power_retention_step,
)
