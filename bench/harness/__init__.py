"""The benchmark's harness: cells found by name, seeds, what the drivers
share, the RBF configurations' plain reference and the trace reduction."""
