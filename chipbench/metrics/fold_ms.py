"""Fold: device milliseconds per traced call of the ops under the program's
``fold`` scope (the insert's slot tenancy, ring-slot reset and scatter, and
the watermark), averaged over the cell's chips (``chipbench/scopes.py``).
Nothing where the program names no such layer."""
from chipbench import scopes


def read(ctx):
    got = scopes.read(ctx)
    if got is None or not any(scopes.layer_of(p) == "fold" for p in got.ms):
        return None
    return got.layer_ms("fold")
