"""Deep-model subsystem: architectures, jit-compiled batched inference
(the CNTKModel equivalent), in-process SPMD training (the cntk-train
equivalent), transfer-learning featurization, and a model zoo.

Reference modules replaced: src/cntk-model/ (CNTKModel.scala),
src/cntk-train/ (CNTKLearner.scala), src/image-featurizer/
(ImageFeaturizer.scala), src/downloader/ (ModelDownloader.scala).
"""

import time as _time

_IMPORT_STARTED = _time.monotonic()     # -> a `package.import` span, below

from .models import (
    MLP,
    SimpleCNN,
    ResNet,
    resnet20_cifar,
    resnet50,
    ARCHITECTURES,
    make_model,
    ModelBundle,
)
from .runner import DeepModelTransformer
from .trainer import DNNLearner, DNNModel
from .featurizer import ImageFeaturizer
from .zoo import ModelSchema, ModelDownloader, retry_with_timeout

__all__ = [
    "MLP",
    "SimpleCNN",
    "ResNet",
    "resnet20_cifar",
    "resnet50",
    "ARCHITECTURES",
    "make_model",
    "ModelBundle",
    "DeepModelTransformer",
    "DNNLearner",
    "DNNModel",
    "ImageFeaturizer",
    "ModelSchema",
    "ModelDownloader",
    "retry_with_timeout",
]

from ..observability.tracing import record_import as _record_import  # noqa: E402

_record_import(__name__, _IMPORT_STARTED)
