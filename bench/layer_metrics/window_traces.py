"""Engine layer: jaxpr traces inside the measured window.  A program that
is jitted afresh on every call (ProcessVertices' closure on LOCAL) traces
again each time; the target is none."""


def read(window):
    return window.compiles["traces"]
