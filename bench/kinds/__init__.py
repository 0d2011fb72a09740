"""Step loops, one per kind of traffic (the ``kind`` of a mix file)."""
