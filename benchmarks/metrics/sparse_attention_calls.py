"""Attention calls that took the indexer's selection, counted while tracing
(set-up and window): the sparse layers of a step for every time the step is
traced (a run with ``--trace 1`` traces it a second time, for the program's
scope tables, as ``window_attention_calls`` reads). 0 says the sparse layers
ran dense (rows no longer than the indexer's top-k). Source: the program's
``sparse_attention_call_total``, which every attention call declares;
nothing from a program that has no such counter."""

COUNTER = "sparse_attention_call_total"


def read(ctx):
    from perceiver_io_tpu.observability import default_registry

    value = default_registry().counters().get(COUNTER)
    return None if value is None else float(value)
