"""The benchmark of perceiver_io_tpu: the yardstick later PRs are held to.

``BENCHMARK.json`` at the root of the repository names the cells, the
configurations and the metrics; everything that belongs to one of them is a
file here that the harness finds by that name (``configs/<configuration>.json``,
``traffic/mixes/<traffic>.json``, ``metrics/<metric>.py``). ``run.py`` runs
one cell once.
"""
