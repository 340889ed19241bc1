"""One module per ``pydcop generate`` family, found by the name in a
configuration's ``generator["family"]``; each has ``generate(spec,
seed)``, ``shapes(spec)``, ``small(spec)`` and ``check(spec)``."""
