"""Chip benchmark: one cell (a model configuration under one traffic mix) run
once on the TPU, with its metrics, its trace reduction and its check against a
plain float32 reference. Entry point: ``python3 bench/run.py``."""
