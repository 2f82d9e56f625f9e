"""Which Pallas kernels are the LoRA kernels, by the names they carry:
the kernel functions of ``kernels/ragged.py`` (ragged family) and of
``kernels/fused_lora.py`` (masked family), or any kernel named for LoRA.
The dequantizing matmul is not one."""
from __future__ import annotations

from typing import Dict, Sequence

import xtrace

LORA = {"_fwd_kernel", "_dgrad_kernel", "_rank_major_kernel",
        "_wgrad_kernel", "_fused_lora_kernel", "_grouped_wgrad_kernel",
        "_grouped_mm_kernel"}


def is_lora(name: str) -> bool:
    return name in LORA or ("lora" in name.lower()
                            and "dequant" not in name.lower())


def lora_time_ns(ops: Sequence[xtrace.Op], kernels: Dict[str, str],
                 lo: int, hi: int) -> int:
    """Device time of one chip's LoRA kernels inside [lo, hi]."""
    return xtrace.op_time(
        ops, lo, hi,
        lambda o: o.is_pallas and is_lora(kernels.get(o.name, "")))
