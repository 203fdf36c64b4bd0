"""What a step is required to compute and move, from shapes alone: one
module per architecture (found by the configuration's ``reference`` name)
lists a step's matrix products and attention calls; ``work.py`` turns them
into operations, bytes and least times."""
