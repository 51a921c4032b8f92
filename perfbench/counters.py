"""Readers of the program's own counters, shared by metric files.

The megakernel counts, per launch and rounds included, the live select
entries of its PERMUTE steps that ran as one dense GF(2) product
(``megakernel_entries_dense``) and those it walked one row at a time
(``megakernel_entries_walked``).  A program that counts neither, as
before dense products existed, reads nothing.
"""


def dense_select_share(ctx):
    """Dense / (dense + walked) x 100 over the window's launches."""
    dense = ctx.counters.get("megakernel_entries_dense")
    walked = ctx.counters.get("megakernel_entries_walked")
    if dense is None and walked is None:
        return None
    total = (dense or 0) + (walked or 0)
    return (dense or 0) / total * 100 if total else None
