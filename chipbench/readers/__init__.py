"""One module per source of per-layer numbers, found by the name in a
metric file's ``reader``; each has ``read(capture, **args)``, which
returns a number, or None where there is nothing to read."""
