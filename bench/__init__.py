"""The chip benchmark: one command per run, everything found by name.

See ``bench/run.py`` and the repository's ``BENCHMARK.json``."""
