"""codec_kernel_ms (ms/round): device time of the quantize kernel's events
(trace_reduce.KERNELS["quantize"]) per round, mean over the chips.
Nothing to read where the cell's wire is not quantized."""


def read(ctx):
    s = ctx["trace"]["kernel_s"]["quantize"]
    if s <= 0:
        return None
    return 1e3 * s / ctx["rounds"]
