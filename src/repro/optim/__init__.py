from . import adamw
from .adamw import AdamWConfig, AdamWState, make_train_step

__all__ = ["adamw", "AdamWConfig", "AdamWState", "make_train_step"]
