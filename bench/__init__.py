"""The benchmark of the batch backend on the chip (see ``run.py``)."""
