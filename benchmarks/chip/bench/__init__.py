"""Harness of the chip benchmark: spec loading, traffic, the serving window,
trace capture and reduction, and the correctness check."""
