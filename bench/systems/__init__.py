"""One driver per kind of configuration (``"system"`` in its file)."""
