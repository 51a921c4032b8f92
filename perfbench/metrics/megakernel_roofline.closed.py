"""The megakernel's share of its memory-bound roofline."""

from perfbench.readers import megakernel_roofline as read  # noqa: F401
