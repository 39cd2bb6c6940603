"""Benchmark harness for the densest-subgraph system (see harness.py)."""
