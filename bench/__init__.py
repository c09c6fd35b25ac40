"""The chip benchmark: ``BENCHMARK.json`` at the root of the repo names its
cells and metrics, and ``bench/run.py`` runs one cell."""
