"""95th percentile of time to first token over every request that arrived
in the window, each timed from when it was due (ms). Exact, with numpy's
linear interpolation, as `serving/engine.py`'s `summarize` takes it."""
import numpy as np


def read(rec):
    if not rec.get("ttft_s"):
        return None
    return float(np.percentile(rec["ttft_s"], 95)) * 1e3
