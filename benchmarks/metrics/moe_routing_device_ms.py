"""Device milliseconds a traced training step spends around the experts'
products: the router and its top-k (``router``), sorting the token-expert
pairs and gathering their rows (``dispatch``), and putting the rows back by
token under their weights (``combine``); forward, recomputation and backward."""
from benchmarks import phases


def read(ctx):
    return phases.phase_ms(ctx, ("router", "dispatch", "combine"))
