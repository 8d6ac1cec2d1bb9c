"""Client layer: 99th percentile of how late the generator sent each
request due in the window, against its due time (ms). A late generator
shows here, not as a slow server."""
import numpy as np


def read(ctx):
    if ctx.late_ms.size == 0:
        return None
    return float(np.percentile(ctx.late_ms, 99))
