"""90th percentile, over every refresh of the window, of the time from a new
live refit to its validated table published (host clock)."""
import numpy as np


def read(ctx):
    lat = ctx.samples.get("latencies")
    return float(np.percentile(lat, 90)) if lat else None
