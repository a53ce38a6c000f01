from .attention import attention
from .norms import layer_norm, rms_norm
from .quantization import dequantize_int8, quantize_int8
from .registry import available_backends, get_op, register, set_backend
from .rotary import apply_rotary, rope_frequencies

from . import pallas  # noqa: F401  (registers the Pallas kernel tier)

__all__ = ["attention", "layer_norm", "rms_norm", "available_backends", "get_op",
           "register", "set_backend", "apply_rotary", "rope_frequencies"]
