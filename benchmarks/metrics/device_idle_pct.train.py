"""Share of the traced training steps in which no operation ran on the
device, in percent: 1 - union of device-op intervals over the traced span."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or trace.idle_share() is None:
        return None
    return 100.0 * trace.idle_share()
