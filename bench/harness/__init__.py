"""The harness shared by every cell (see ``bench/run.py``)."""
