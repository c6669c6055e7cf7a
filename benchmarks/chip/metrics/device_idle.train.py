"""Device: 1 - busy / window over the traced part of a training window,
averaged over the chips (%)."""


def read(rec):
    tr = rec.get("trace")
    if not tr or "train_tokens" not in rec:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
