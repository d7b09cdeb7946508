"""Inference engine: jit-compiled prefill/decode over a paged KV block
pool, bucketed shapes, on-device sampling, and token streaming. This is the
TPU-native replacement for the reference's torch `model.generate` thread
(reference hf.py:84-108)."""

from .engine import EngineConfig, GenerationResult, InferenceEngine  # noqa: F401
from ..models.support import FeatureUnsupported  # noqa: F401
from .sampling import sample  # noqa: F401
