"""RT-LM chip benchmark: one run of one cell per command (``run.py``)."""
