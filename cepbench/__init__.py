"""On-chip benchmark of the CEP runtime; ``run.py`` is its command."""
