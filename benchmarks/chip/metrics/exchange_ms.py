"""exchange_ms (ms/round): device time of the collective ops per round,
mean over the chips.  Nothing to read where no collective ran."""


def read(ctx):
    s = ctx["trace"]["collective_s"]
    if s <= 0:
        return None
    return 1e3 * s / ctx["rounds"]
