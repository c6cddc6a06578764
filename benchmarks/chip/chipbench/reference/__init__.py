"""Plain references the benchmark compares the trainer with."""
