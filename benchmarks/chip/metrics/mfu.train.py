"""Training step: forward and backward FLOPs per token (`flops.py`, no
recomputation) times the (n_micro + 1) passes each token of an outer step
makes, for every outer step completed in the window, over the window times
the chips times the chip's bf16 peak (%)."""
import flops


def read(rec):
    if not rec.get("peak"):
        return None
    if "train_tokens" not in rec:
        return None
    per_tok = flops.train_flops_per_token(rec["spec"], rec["seq_len"])
    passes = rec["passes_per_token"]
    return 100.0 * rec["train_tokens"] * per_tok * passes / (
        rec["window_s"] * rec["chips"] * rec["peak"]["bf16_flops"])
