"""Plain references of the configurations' models, one module each."""
