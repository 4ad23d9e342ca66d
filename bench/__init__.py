"""The chip benchmark of the study engine (see README.md)."""
