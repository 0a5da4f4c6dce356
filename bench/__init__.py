"""On-chip benchmark of the served path: open-loop multi-tenant traffic
through ``ServingEngine``.  ``bench/run.py`` runs one cell of
``BENCHMARK.json`` once; ``bench/repeat.py`` runs a cell over several
seeds or offered rates, one process each."""
