"""The benchmark: cells, configurations, traffic, readers and the harness.

Everything a later PR may not change lives here (see README.md)."""
