"""deepspeed_tpu — a TPU-native distributed training & inference framework.

Brand-new JAX/XLA/Pallas implementation of the capability surface of the
reference DeepSpeed repo (see SURVEY.md): ZeRO-style sharded training,
data/tensor/pipeline/expert/sequence parallelism over one named device mesh,
fused optimizers and kernels, checkpoint/universal-resume, profiling, and a
continuous-batching inference engine.

Public API (mirrors /root/reference/deepspeed/__init__.py):
    initialize(...)      -> (engine, optimizer, dataloader, lr_scheduler)
    init_inference(...)  -> InferenceEngine
"""
from .version import __version__  # noqa: F401

from . import comm, models, zero  # noqa: F401
from .accelerator import get_accelerator  # noqa: F401
from .config import Config, DeepSpeedConfig  # noqa: F401
from .parallel.topology import MeshConfig, MeshTopology  # noqa: F401
from .utils.logging import log_dist, logger  # noqa: F401


def initialize(*args, **kwargs):
    """Training bring-up (reference deepspeed/__init__.py:69). See
    :func:`deepspeed_tpu.runtime.engine.initialize`."""
    from .runtime.engine import initialize as _init

    return _init(*args, **kwargs)


def init_inference(*args, **kwargs):
    """Inference bring-up (reference deepspeed/__init__.py:291)."""
    from .inference.engine import init_inference as _init

    return _init(*args, **kwargs)
