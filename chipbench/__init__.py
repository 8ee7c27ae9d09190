"""The chip benchmark: one cell, one process, one result line (see README.md)."""
