"""Tokens emitted inside the window, over the window (tokens/s)."""


def read(rec):
    if "tokens_in_window" not in rec:
        return None
    return rec["tokens_in_window"] / rec["window_s"]
