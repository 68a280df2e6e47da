"""The join engine's benchmark (see ``bench/run.py`` and ``BENCHMARK.json``)."""
