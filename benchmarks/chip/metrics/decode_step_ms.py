"""Model step: device time per decode dispatch in the trace (ms)."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["program_n"].get("decode"):
        return None
    return tr["program_s"]["decode"] / tr["program_n"]["decode"] * 1e3
