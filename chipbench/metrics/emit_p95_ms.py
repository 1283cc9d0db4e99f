"""95th percentile, over every call of the window, of the host-clock time
from the start of the call's feed to its windows being on the host."""
import numpy as np


def read(ctx):
    return 1e3 * float(np.percentile([c.seconds for c in ctx.window.calls], 95))
