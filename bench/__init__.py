"""Benchmark of the Dumpy index on the chip (see ``bench/run.py``)."""
