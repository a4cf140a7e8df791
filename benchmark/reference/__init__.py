"""Plain float32 references, one module per configuration, found by name."""
