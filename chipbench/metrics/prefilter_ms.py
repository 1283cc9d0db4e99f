"""TopK pre-filter: device milliseconds per traced call of the ops under a
``prefilter`` scope in the program's ``fold`` layer (the masking and
``lax.top_k`` of each window offset, and the test of whether the batch can
take the fast join), averaged over the cell's chips (``chipbench/scopes.py``).
Nothing where the program names no such scope."""
from chipbench import scopes


def read(ctx):
    got = scopes.read(ctx)
    ms = [v for p, v in (got.ms if got else {}).items()
          if scopes.layer_of(p) == "fold" and p.rsplit("/", 1)[-1] == "prefilter"]
    return sum(ms) if ms else None
