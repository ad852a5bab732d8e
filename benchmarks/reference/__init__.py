"""Plain references: what the program's results are compared with."""
