"""Plain float32 references that decide each cell's ``correct``."""
