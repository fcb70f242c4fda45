"""Plain references of the models, importing nothing of the program."""
