"""Architecture configs: one module per assigned arch + the paper's MLPs."""
from .base import ARCH_IDS, ArchConfig, get_config

__all__ = ["ARCH_IDS", "ArchConfig", "get_config"]
