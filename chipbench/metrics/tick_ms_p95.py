"""95th percentile of every tick's time in the window, in ms.  A tick runs
from the moment its metric batch is ready to the moment its decisions
array is on the host."""
import numpy as np


def read(ctx):
    return float(np.percentile(np.asarray(ctx.tick_s) * 1e3, 95))
