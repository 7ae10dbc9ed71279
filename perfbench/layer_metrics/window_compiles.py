"""Compilations inside the measured window.  There should be none: any
makes the run ``correct: false``."""


def read(artifacts):
    return artifacts.get("window_compiles")
