"""Model registry: HF `model_type` string -> RingModel subclass.

Reference: src/dnet/core/models/__init__.py:13-35 (subclass scan).
"""

from __future__ import annotations

from typing import Type

from dnet_tpu.models.base import ModelConfig, RingModel


def _all_subclasses(cls: type) -> list[type]:
    out = []
    for sub in cls.__subclasses__():
        out.append(sub)
        out.extend(_all_subclasses(sub))
    return out


def get_ring_model_cls(model_type: str) -> Type[RingModel]:
    # Import concrete models so subclasses are registered.
    from dnet_tpu.models import llama  # noqa: F401

    try:
        from dnet_tpu.models import qwen3  # noqa: F401
    except ImportError:
        pass
    try:
        from dnet_tpu.models import gpt_oss  # noqa: F401
    except ImportError:
        pass
    try:
        from dnet_tpu.models import deepseek_v2  # noqa: F401
    except ImportError:
        pass
    try:
        from dnet_tpu.models import mixtral  # noqa: F401
    except ImportError:
        pass
    try:
        from dnet_tpu.models import qwen2  # noqa: F401
    except ImportError:
        pass
    try:
        from dnet_tpu.models import qwen3_moe  # noqa: F401
    except ImportError:
        pass
    try:
        from dnet_tpu.models import cohere2_moe  # noqa: F401
    except ImportError:
        pass

    try:
        from dnet_tpu.models import brumby  # noqa: F401
    except ImportError:
        pass
    try:
        from dnet_tpu.models import qwen3_next  # noqa: F401
    except ImportError:
        pass
    try:
        from dnet_tpu.models import minicpm_sala  # noqa: F401
    except ImportError:
        pass
    try:
        from dnet_tpu.models import mellum  # noqa: F401
    except ImportError:
        pass

    for sub in _all_subclasses(RingModel):
        if getattr(sub, "model_type", None) == model_type:
            return sub
    raise ValueError(f"unsupported model_type: {model_type!r}")


__all__ = ["ModelConfig", "RingModel", "get_ring_model_cls"]
