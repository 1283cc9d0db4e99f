"""TopK set join: device milliseconds per traced call of the ops under a
``setjoin`` scope in the program's ``fold`` layer (the 2k sort-join of each
window offset's candidates with its ring row, and the rows' write-back),
averaged over the cell's chips (``chipbench/scopes.py``).  Nothing where the
program names no such scope."""
from chipbench import scopes


def read(ctx):
    got = scopes.read(ctx)
    ms = [v for p, v in (got.ms if got else {}).items()
          if scopes.layer_of(p) == "fold" and p.rsplit("/", 1)[-1] == "setjoin"]
    return sum(ms) if ms else None
