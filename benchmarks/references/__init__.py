"""Plain references, one per family, named by a configuration's ``reference``."""
