"""The plain side of each model family, ``bench/reference/<family>.py``:
the float32 references that decide each cell's ``correct``."""
