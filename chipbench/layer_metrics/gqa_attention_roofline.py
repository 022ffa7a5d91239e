"""Forward + backward programs: the least time for the causal attention
products the traced steps require (flops/nemotron_h.py: the lower
triangle, 32 query heads, scores and context at 128 columns each, forward
and the two gradients of each, nothing recomputed) at the chip's peak,
over the device time under ``mx.attention``. Bound: MXU. The reading is
``attention_roofline``'s own, listed for this family's cells."""

from .attention_roofline import read  # noqa: F401
