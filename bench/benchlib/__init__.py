"""The benchmark's own yardstick: traffic, arithmetic, trace reduction,
files found by name, and the run of one cell."""
