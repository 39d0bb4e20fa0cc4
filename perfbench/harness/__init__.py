"""Chip benchmark harness of the served planner (see ``perfbench/run.py``)."""
