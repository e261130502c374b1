"""Plain references of the model families that configurations name."""
