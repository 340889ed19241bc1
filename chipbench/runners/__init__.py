"""One module per kind of configuration, found by the name in the
configuration file's ``kind``; each has ``run(cell) -> dict``."""
