"""Engine layer: backend compilations inside the measured window.  The
warm-up compiles every shape the window uses, so what is left is a program
the engine jits afresh on every call (ProcessVertices' closure on LOCAL),
which compiles again each time as it does for a user; the target is
none."""


def read(window):
    return window.compiles["compiles"]
