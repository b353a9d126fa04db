"""On-chip benchmark of the training and serving stack: harness, references, yardstick."""
