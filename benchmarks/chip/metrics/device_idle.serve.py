"""Device: 1 - busy / window over the traced part of a serving window (%)."""


def read(rec):
    tr = rec.get("trace")
    if not tr or "tokens_in_window" not in rec:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
