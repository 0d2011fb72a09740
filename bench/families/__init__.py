"""Model families, one module per ``model_type`` of a configuration file,
found by name (``bench/spec.py``)."""
