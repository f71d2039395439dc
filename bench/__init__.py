"""The chip benchmark of the LANNS serving path (``python bench/run.py``)."""
