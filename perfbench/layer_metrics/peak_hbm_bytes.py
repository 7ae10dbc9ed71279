"""Peak bytes in use on the fullest chip, as ``memory_stats()`` has it."""


def read(artifacts):
    return artifacts["device"].get("memory_peak_bytes") or None
