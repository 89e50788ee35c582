"""Model FLOP utilisation of serving: the FLOPs of every token the
traced window processed (each decode token with its context, each real
prompt token of a prefill with its causal context; bucket padding not
counted), over the window, the chips and each chip's bf16 peak. The
configuration's reference module counts the FLOPs of its architecture."""
from benchlib import spec


def read(rec):
    if rec["kind"] != "serve" or not rec["traced_contexts"]:
        return None
    cfg = rec["cfg"]
    ref = spec.reference(cfg)
    need = sum(ref.decode_flops(cfg, ctx) for ctx in rec["traced_contexts"])
    need += ref.prefill_flops(cfg, rec["traced_prompts"])
    peak = rec["chips"] * rec["peaks"]["bf16_flops_per_s"]
    return 100.0 * need / (rec["window_s"] * peak)
