"""Model zoo registry: architecture name -> constructor.

The TPU-native analogue of the reference's pretrained-model repository
schema (``downloader/src/main/scala/Schema.scala:31-92``): every
architecture registers under a stable name with its input spec and the
ordered layer names available for feature extraction (the reference's
``layerNames``/``cutOutputLayers`` contract, ``ImageFeaturizer.scala:85-120``).
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

_ZOO: Dict[str, Callable] = {}


def register_model(name: str):
    def wrap(fn):
        _ZOO[name] = fn
        return fn
    return wrap


def build_model(name: str, **kwargs):
    if name not in _ZOO:
        raise KeyError(f"unknown architecture {name!r}; have {sorted(_ZOO)}")
    return _ZOO[name](**kwargs)


def available_models() -> List[str]:
    return sorted(_ZOO)


# populate the registry
from mmlspark_tpu.models.zoo import resnet as _resnet  # noqa: E402,F401
from mmlspark_tpu.models.zoo import mlp as _mlp  # noqa: E402,F401
from mmlspark_tpu.models.zoo import cnn1d as _cnn1d  # noqa: E402,F401
from mmlspark_tpu.models.zoo import vit as _vit  # noqa: E402,F401
from mmlspark_tpu.models.zoo import transformer as _transformer  # noqa: E402,F401
from mmlspark_tpu.models.zoo import moe as _moe  # noqa: E402,F401
from mmlspark_tpu.models.zoo import decoder as _decoder  # noqa: E402,F401
from mmlspark_tpu.embed import model as _recommender  # noqa: E402,F401
