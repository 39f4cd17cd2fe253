"""Plain references, their comparisons with the program, and the limits
that decide ``correct``."""
