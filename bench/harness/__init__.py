"""The benchmark's harness: traffic, timing, trace reduction, reference
and comparison.  `bench/run.py` is the entry point."""
