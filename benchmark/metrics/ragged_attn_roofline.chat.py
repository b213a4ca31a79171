"""Kernels: ``ragged_paged_attention``.  The least time the chip could
take for the traced steps' attention (FLOPs and bytes from each pack's
real span and context lengths, per layer: ``counts.attention_*``; the
larger of FLOPs over peak and bytes over bandwidth, step by step) over the
device time of the kernel's events in the trace, in percent."""
from harness.readers import ragged_attention_roofline as read  # noqa: F401
