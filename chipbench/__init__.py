"""Chip benchmark of the hinted LSM store and the tiered serving engine."""
