"""A per-layer metric added as a file only (the data-driven test)."""


def read(obs):
    return 42.0
