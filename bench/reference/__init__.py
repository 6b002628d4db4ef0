"""Plain float32 references the benchmark compares the served tokens with."""
