"""95th percentile over requests of (t_done - t_first) / (tokens - 1), the
mean gap between a request's output tokens (ms)."""
import numpy as np


def read(rec):
    if not rec.get("tpot_s"):
        return None
    return float(np.percentile(rec["tpot_s"], 95)) * 1e3
