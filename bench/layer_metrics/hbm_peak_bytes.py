"""Device layer: ``memory_stats()["peak_bytes_in_use"]`` of the fullest
chip, read after the window."""


def read(window):
    return window.memory_peak_bytes or None
