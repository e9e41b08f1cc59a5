"""The benchmark's harness: cells found by name, seeded data, the two
drivers, the plain reference and the trace reduction."""
