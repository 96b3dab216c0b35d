"""step_mfu (%): model FLOPs of the traced window's rounds (one forward and
backward per worker per round, counts.model_flops_per_round) over the
window's length on the trace's clock (trace_reduce: the same window whose
busy share device_idle_share reads), over the chips' bfloat16 peak
(peaks.py)."""


def read(ctx):
    window_s = ctx["trace"]["window_s"]
    if window_s <= 0:
        return None
    flops = ctx["counts"].model_flops_per_round(ctx["cfg"], ctx["traffic"])
    rate = flops * ctx["rounds"] / window_s
    return 100.0 * rate / (ctx["chips"] * ctx["peaks"]["flops"])
