"""Chip benchmark of the persistence-diagram system (see ``run.py``)."""
