"""Shared parts of the benchmark harness: loading files by name, the device
gate, the compile log, seeded weights, traffic, and the result line. Nothing
here knows a cell, a configuration or a metric by name."""
