"""Shared code of the benchmark: it holds no cell's, configuration's,
traffic mix's or metric's name.  What belongs to one of those is a data
file found by the name `BENCHMARK.json` gives (see ../README.md)."""
