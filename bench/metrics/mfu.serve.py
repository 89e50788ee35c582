"""Model FLOP utilisation of serving: the FLOPs of every token the
traced window processed (each decode token with its context, each real
prompt token of a prefill with its causal context; bucket padding not
counted), over the window, the chips and each chip's bf16 peak."""
from benchlib import flops


def read(rec):
    if rec["kind"] != "serve" or not rec["traced_contexts"]:
        return None
    cfg = rec["cfg"]
    need = sum(flops.decode_flops(cfg, ctx)
               for ctx in rec["traced_contexts"])
    need += flops.prefill_flops(cfg, rec["traced_prompts"])
    peak = rec["chips"] * rec["peaks"]["bf16_flops_per_s"]
    return 100.0 * need / (rec["window_s"] * peak)
