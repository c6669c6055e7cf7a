"""Decode program: the least time the chip could take for the traced decode
dispatches (the larger of FLOPs over peak and bytes over HBM bandwidth, per
dispatch, from `flops.py`), over their device time in the trace (%)."""
import flops


def read(rec):
    if not rec.get("peak"):
        return None
    tr, lanes = rec.get("trace"), rec.get("counts", {}).get(
        "traced_decodes")
    if not tr or not lanes or not tr["program_s"].get("decode"):
        return None
    least = sum(flops.decode_least_s(rec["spec"], p, rec["peak"])
                for p in lanes)
    return 100.0 * least / tr["program_s"]["decode"]
