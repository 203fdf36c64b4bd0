"""Windows: one module per kind of cell (``train``, ``serve``), found by the
name a traffic mix gives under ``driver``."""
