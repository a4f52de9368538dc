"""The harness behind ``bench/run.py``."""
