"""On-chip benchmark of the client-selection engine (chipbench/run.py)."""
