"""Model FLOP utilisation of training: the FLOPs the useful tokens of
the traced window require (forward and backward, no recomputation),
over the window, the chips and each chip's bf16 peak. The
configuration's reference module counts the FLOPs of its architecture."""
from benchlib import spec


def read(rec):
    if rec["kind"] != "train" or not rec["traced_tokens"]:
        return None
    need = rec["traced_tokens"] * spec.reference(
        rec["cfg"]).train_flops_per_token(rec["cfg"], rec["mix"]["seq_len"])
    peak = rec["chips"] * rec["peaks"]["bf16_flops_per_s"]
    return 100.0 * need / (rec["window_s"] * peak)
