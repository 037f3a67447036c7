from repro.optim.base import (Optimizer, add_decayed_weights, apply_updates,
                              chain, clip_by_global_norm, scale,
                              scale_by_schedule)
from repro.optim.optimizers import adafactor, adamw, sgd
from repro.optim.schedules import constant, inverse_time, warmup_cosine

__all__ = [
    "Optimizer", "add_decayed_weights", "apply_updates", "chain",
    "clip_by_global_norm", "scale", "scale_by_schedule", "adafactor",
    "adamw", "sgd", "constant", "inverse_time", "warmup_cosine",
]
