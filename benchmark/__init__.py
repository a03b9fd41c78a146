"""The repository's benchmark: everything ``BENCHMARK.json`` names lives
here and is found by name (see ``run.py``)."""
