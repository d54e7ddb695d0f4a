"""The benchmark's own code: harness, traffic generators, trace reduction,
operation counts and the comparisons that decide ``correct``."""
