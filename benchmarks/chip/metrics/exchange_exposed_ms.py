"""exchange_exposed_ms (ms/round): the part of the collective ops' device
time during which no other op ran on that chip, per round, mean over the
chips.  Nothing to read where no collective ran."""


def read(ctx):
    if ctx["trace"]["collective_s"] <= 0:
        return None
    return 1e3 * ctx["trace"]["collective_exposed_s"] / ctx["rounds"]
