"""ArrayFlex kernel's share of its roofline: summed least time (max of FLOPs at peak bf16 rate, bytes at peak HBM rate; logical shapes) of every kernel call in the traced interval over those calls' device time from the trace, in %."""
from chipbench.readings import gemm_roofline as read  # noqa: F401
