"""Storage layer: share of the chunk reads whose decode ran in the Pallas
varint kernels on the device."""


def read(window):
    dev = window.counter("measured_chunks_device_decoded")
    reads = window.counter("measured_chunks_read")
    if dev is None or not reads:
        return None
    return 100.0 * dev / reads
