"""The selection server's chip benchmark (see run.py and PERF.md)."""
