"""Collectives: megabytes per traced call that the chip's collectives
delivered, from the result shape in the instruction text of each collective
op event (a synchronous op, or the ``-done`` half of an asynchronous one,
each counted once), averaged over the cell's chips.  Nothing where no such
op ran."""
from chipbench import scopes


def read(ctx):
    got = scopes.read(ctx)
    return got.collective_mb if got is not None and got.collective_mb > 0 else None
