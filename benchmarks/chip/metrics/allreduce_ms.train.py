"""Collectives: all-reduce device time per outer step on the first chip, in
the trace (ms)."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["program_n"].get("train"):
        return None
    return tr["allreduce_s"] / tr["program_n"]["train"] * 1e3
