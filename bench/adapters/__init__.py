"""The program side of each model family: ``bench/adapters/<family>.py``."""
