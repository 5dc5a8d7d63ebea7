from .checkpoint import CheckpointManager
from .cnn import make_cnn_train_step
from .compile_cache import enable_compile_cache
from .straggler import StragglerMonitor
from .trainer import make_prefill_step, make_serve_step, make_train_step

__all__ = [
    "CheckpointManager", "StragglerMonitor", "enable_compile_cache",
    "make_cnn_train_step", "make_prefill_step", "make_serve_step",
    "make_train_step",
]
