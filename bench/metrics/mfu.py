"""Model FLOP utilization: the forward FLOPs of the prompt and output tokens
processed in the window, over the window times the chip's bf16 peak, in %."""
from readers import mfu as read  # noqa: F401
