"""Pallas TPU kernels — the native-kernel tier of the op registry.

Reference parity: the reference ships CUDA kernels under ``csrc/`` (fused
softmax/attention in ``csrc/transformer``, norms in
``csrc/transformer/inference/csrc``, quantization in ``csrc/quantization``)
loaded through the OpBuilder system. Here the native tier is Pallas: blockwise
kernels that run on the TPU MXU/VPU out of VMEM, registered under
``backend="pallas"`` in :mod:`deepspeed_tpu.ops.registry` (preferred over XLA
on TPU; on CPU they run in interpret mode when explicitly selected).
"""

from . import flash_attention  # noqa: F401
from . import norms  # noqa: F401
from . import quantize  # noqa: F401
from . import paged_attention  # noqa: F401 (registers ops)
from . import grouped_matmul  # noqa: F401 (registers ops)
