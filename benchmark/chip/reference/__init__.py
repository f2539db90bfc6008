"""Plain references, one per architecture.  Nothing here imports the program."""
