"""Prefill program: the least time the chip could take for the traced
prefill dispatches (per dispatch the larger of its FLOPs over peak and its
bytes over HBM bandwidth, from the architecture's counts; FLOPs alone for
an architecture that counts no prefill bytes), over their device time in
the trace (%). A dispatch's FLOPs sum over its rows; its bytes are its
costliest row's, since the rows share the weights they read."""
import common


def read(rec):
    if not rec.get("peak"):
        return None
    tr, span, lay = rec.get("trace"), rec.get("trace_span"), rec.get(
        "layers")
    if not tr or not span or not lay or not tr["program_s"].get("prefill"):
        return None
    spec, peak = rec["spec"], rec["peak"]
    arch = common.arch(spec)
    nbytes = getattr(arch, "prefill_bytes", None)
    a, b = span
    least = 0.0
    for t, rows in lay["prefills"]:
        if not a <= t <= b or not rows:
            continue
        s = sum(arch.prefill_flops(spec, st, n) for st, n in rows) \
            / peak["bf16_flops"]
        if nbytes is not None:
            s = max(s, max(nbytes(spec, st, n) for st, n in rows)
                    / peak["hbm_bytes_per_s"])
        least += s
    if not least:
        return None
    return 100.0 * least / tr["program_s"]["prefill"]
