from repro.train.trainer import (EpochTimes, TrainState, Trainer,
                                 make_train_step, online_epochs)
from repro.train.online import (CacheStats, EpochStats, OnlineTrainer,
                                SignatureCache, make_family)
from repro.train import checkpoint
from repro.train.fault import Heartbeat, RestartStats, run_with_restarts

__all__ = [
    "EpochTimes", "TrainState", "Trainer", "make_train_step",
    "online_epochs", "CacheStats", "EpochStats", "OnlineTrainer",
    "SignatureCache", "make_family", "checkpoint", "Heartbeat",
    "RestartStats", "run_with_restarts",
]
