"""The windows: one module per kind of traffic (``driver`` in a traffic file)."""
