"""codec_roofline (%): the least time the codec's work needs at the HBM
peak (counts.codec_min_bytes_per_round: theta and the previous hat read in
float32, the levels written at the wire width, each worker once per round)
over the quantize kernel's device time, summed over the chips."""


def read(ctx):
    t = ctx["trace"]
    kernel_s = t["kernel_s"]["quantize"] * t["devices"]
    least = ctx["counts"].codec_min_bytes_per_round(ctx["cfg"], ctx["traffic"])
    if kernel_s <= 0 or least <= 0:
        return None
    least_s = least * ctx["rounds"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
