"""On-chip benchmark of FLySTacK; see bench/run.py."""
