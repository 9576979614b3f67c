"""mmlspark_tpu — a TPU-native ML pipeline framework.

A ground-up rebuild of the capability set of SynapseML/MMLSpark (reference:
Scala/Spark + JNI-native compute) as a JAX/XLA/Pallas-first framework:
columnar DataFrames feeding padded device batches, Estimator/Transformer
pipelines, ONNX→JAX compiled inference, distributed histogram-GBDT training
over a device mesh, explainers, featurization, serving, and HTTP transformers.
"""

__version__ = "0.1.0"

import os as _os

from .core import (DataFrame, Estimator, Model, Pipeline, PipelineModel,
                   PipelineStage, Transformer, concat)

if _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    # the cache is placed from outside and JAX reads the variable itself;
    # this only zeroes its size/time gates so small programs are kept too
    from .ops.compile_cache import enable_persistent_cache as _epc
    _epc()

__all__ = ["DataFrame", "concat", "PipelineStage", "Transformer", "Estimator",
           "Model", "Pipeline", "PipelineModel", "__version__"]
