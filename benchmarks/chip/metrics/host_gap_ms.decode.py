"""Runner host path: median device-idle time between consecutive decode
dispatches in the trace, less what other programs ran in between (ms)."""
import numpy as np


def read(rec):
    gaps = rec.get("trace", {}).get("between", {}).get("decode")
    if not gaps:
        return None
    return float(np.median(gaps)) * 1e3
