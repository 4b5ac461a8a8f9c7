"""mmlspark_tpu — a TPU-native ML framework with the capabilities of
eisber/mmlspark (pipeline-composable estimators/transformers, distributed
histogram-GBDT, a jit-compiled deep-model runner/trainer, image pipelines,
auto-featurization, hyperparameter tuning, evaluation, interpretation, a SAR
recommender, HTTP integration, and low-latency serving) built on
JAX / XLA / Pallas / jax.sharding."""

import time as _time

_IMPORT_STARTED = _time.monotonic()     # -> a `package.import` span, below

__version__ = "0.1.0"

import os as _os


def _place_compile_cache() -> None:
    """Point JAX's persistent compilation cache at one fixed place before
    anything compiles. `JAX_COMPILATION_CACHE_DIR` wins: when it is set
    JAX reads it itself and nothing is set here. Otherwise the cache lives
    in `.jax_cache` next to the package — the path is part of every cache
    key's usefulness (a directory that moves never hits), so no temp name,
    pid or timestamp. Spawned workers import this package and so follow
    the same rule."""
    if _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), ".jax_cache"))


_place_compile_cache()

from . import core, parallel  # noqa: E402


def __getattr__(name):
    # heavy subsystems import lazily so `import mmlspark_tpu` stays fast
    if name in ("nn", "image", "gbdt", "ops", "automl", "text",
                "recommendation", "io_http", "utils", "plot", "native",
                "parallel", "core", "streaming", "resilience",
                "observability"):
        import importlib

        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


from .core import (
    Table,
    Pipeline,
    PipelineModel,
    Transformer,
    Estimator,
    Model,
    Param,
    Params,
)

# last lines: the import so far as a span of the process-default tracer,
# and JAX's trace, lowering and compile events as spans from here on
from .observability.tracing import (  # noqa: E402
    install_jax_bridge as _install_jax_bridge,
    record_import as _record_import,
)

_install_jax_bridge()
_record_import(__name__, _IMPORT_STARTED)
