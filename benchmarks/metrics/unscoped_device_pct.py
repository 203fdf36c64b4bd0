"""Share, in percent, of the traced device-operation time that the scope tables
cannot place: the instruction is not in the table, or neither its ``op_name``
nor that of anything fused into it has a scope below the jitted step (XLA's
async copies carry no metadata; a copy of a parameter is named by the
parameter). Nothing is guessed from a neighbouring instruction."""
from benchmarks import scopes


def read(ctx):
    return scopes.unscoped_pct(ctx)
