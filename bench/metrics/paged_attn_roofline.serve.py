"""Roofline share of the paged decode attention kernel: the least time
the chip needs for the kernel's calls in the traced window (per call,
the larger of its bytes over HBM bandwidth and its FLOPs over the bf16
peak; bytes are each active sequence's K and V up to its context plus
its query and output) over the kernel's summed device time. Nothing is
read where the trace holds no such kernel."""
from benchlib import flops, trace

# the device trace names a Pallas kernel by its HLO instruction only: the
# paged decode kernel is the tpu_custom_call whose result is one query
# per slot, (slots, 1, heads, head_dim), and whose first operand is the
# (slots, max_blocks) block table
KERNEL = (r"= bf16\[\d+,1,\d+,\d+\]\{[^}]*\} custom-call\(s32\[\d+,\d+\]"
          r".*custom_call_target=\"tpu_custom_call\"")


def read(rec):
    if rec["kind"] != "serve" or not rec["traced_contexts"]:
        return None
    secs, calls = trace.kernel_s(rec["trace"], KERNEL)
    if not calls:
        return None
    cfg, peaks = rec["cfg"], rec["peaks"]
    least = 0.0
    for ctx in rec["traced_contexts"]:
        cost = flops.paged_attention_cost(cfg, ctx)
        least += cfg["num_layers"] * max(
            cost["bytes"] / peaks["hbm_bytes_per_s"],
            cost["flops"] / peaks["bf16_flops_per_s"])
    return 100.0 * least / secs
