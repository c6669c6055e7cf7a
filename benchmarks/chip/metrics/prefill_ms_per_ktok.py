"""Model step: device time of the prefill programs (the batched prefill and
its first-token sampler) per 1000 prompt tokens computed in the trace (ms)."""


def read(rec):
    tr, n = rec.get("trace"), rec.get("counts", {}).get(
        "traced_prefill_tokens")
    if not tr or not n:
        return None
    s = tr["program_s"].get("prefill", 0.0) + tr["program_s"].get("first",
                                                                  0.0)
    return s / n * 1e6
