"""Hand-written TPU kernels (pallas/Mosaic).

The reference has zero native/kernel code (SURVEY §2 native inventory:
"none"); on TPU the kernel obligations come from the target itself —
flash attention tiles that keep the MXU fed from VMEM instead of
materializing [T, S] score matrices in HBM.

On devices that are not TPUs the kernels run in pallas interpret mode
(ops/flash.interpret_off_tpu), so the whole test suite exercises them on
the CPU mesh.
"""

from .flash import flash_attention  # noqa: F401
from .ragged import ragged_paged_attention  # noqa: F401
