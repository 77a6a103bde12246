"""The benchmark: harness, traffic generator, reference and trace
reduction (see ``bench/run.py``)."""
