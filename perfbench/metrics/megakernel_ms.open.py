"""Mean device time of one megakernel launch, from the trace."""

from perfbench.readers import megakernel_ms as read  # noqa: F401
