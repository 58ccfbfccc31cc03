"""Filtered vector search benchmark: harness, yardstick and cells."""
