"""Device-busy milliseconds per edge round: the union of the device's
operation intervals in the traced window over the rounds in it (the
busiest device where there are several)."""


def read(out):
    if out.trace is None or not out.rounds_traced:
        return None
    return 1e3 * max(out.trace.busy_by_device) / out.rounds_traced
