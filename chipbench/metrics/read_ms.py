"""Window read: device milliseconds per traced call of the ops under the
program's ``read`` scope (the window reads after the scan, their gathers
included), averaged over the cell's chips (``chipbench/scopes.py``). Nothing
where the program names no such layer."""
from chipbench import scopes


def read(ctx):
    got = scopes.read(ctx)
    if got is None or not any(scopes.layer_of(p) == "read" for p in got.ms):
        return None
    return got.layer_ms("read")
