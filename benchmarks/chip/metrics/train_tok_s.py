"""Tokens of every outer step completed in the window, over the window
(tokens/s, all chips together)."""


def read(rec):
    if "train_tokens" not in rec:
        return None
    return rec["train_tokens"] / rec["window_s"]
