"""Device: forward FLOPs of every prefill and decode token computed in the
window (`flops.py`), over the window times the chip's bf16 peak (%)."""


def read(rec):
    if not rec.get("peak"):
        return None
    f = rec.get("counts", {}).get("window_flops")
    if not f:
        return None
    return 100.0 * f / (rec["window_s"] * rec["peak"]["bf16_flops"])
