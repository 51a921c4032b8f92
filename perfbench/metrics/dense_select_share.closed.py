"""Share of the megakernel's walked-or-dense select entries that ran as
dense GF(2) products, from the program's counters."""

from perfbench.counters import dense_select_share as read  # noqa: F401
