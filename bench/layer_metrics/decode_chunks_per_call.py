"""Kernels layer (``kernels/varint.py``): chunks decoded on the device per
decode call (``measured_chunks_device_decoded`` over
``measured_device_decode_calls``), that is how many chunks one dispatch
chain and one sync serve.  None where the program has no such counter."""


def read(window):
    calls = window.counter("measured_device_decode_calls")
    chunks = window.counter("measured_chunks_device_decoded")
    if not calls or chunks is None:
        return None
    return chunks / calls
