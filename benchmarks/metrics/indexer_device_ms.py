"""Device milliseconds a traced training step spends under the program's
scope ``indexer``: the sparse layers' indexers, their projections, their
causal scores, the exact top-k selection and the indexer's loss, forward,
recomputation and backward, by the scope on an instruction or, where XLA
left none, in what it holds; the selection and the loss are loops, whose own
events are their bodies' time again and are left out
(``benchmarks/loop_phases.py``). Nothing from a program without the scope."""
from benchmarks import loop_phases


def read(ctx):
    return loop_phases.phase_ms(ctx, "indexer")
