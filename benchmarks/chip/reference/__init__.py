"""Plain float32 reference of the served model (decode semantics)."""
