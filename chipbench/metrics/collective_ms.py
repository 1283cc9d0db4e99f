"""Collectives: device milliseconds per traced call of the ops whose HLO
opcode is a collective (all-gather, all-to-all, all-reduce,
collective-permute, and their start/done halves), as the union of their
intervals, averaged over the cell's chips.  Nothing where no such op ran."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.calls or not any(t.collective_busy_s):
        return None
    return 1e3 * t.mean_over_devices(t.collective_busy_s) / t.calls
