"""Seconds the program took to build its backend (model construction and
random init), timed by the harness around ``get_backend``."""


def read(artifacts):
    return artifacts["setup"].get("backend_init_s")
