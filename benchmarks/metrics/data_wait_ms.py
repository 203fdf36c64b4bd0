"""Mean milliseconds a step of the window waited for the benchmark's stream
to hand out its batch. Source: the benchmark's clock around each hand-out."""


def read(ctx):
    w = ctx["window"]
    if not w.get("steps"):
        return None
    return 1e3 * w["data_wait_s"] / w["steps"]
