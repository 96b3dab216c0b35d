"""wire_bytes_per_round (bytes/round): the trainer's own counter of what
its exchange moves (`wire_bits_per_round` of the step metrics: payload
rows as exchanged, packing and padding included, plus the quantizer's
header), averaged over the traced window's rounds, / 8."""


def read(ctx):
    bits = [float(m["wire_bits_per_round"]) for m in ctx["per_round"]]
    if not bits:
        return None
    return sum(bits) / len(bits) / 8.0
