"""Device milliseconds per step of the loss head (final norm, tied logits
and cross-entropy, forward and backward): the program's ``pipe.head``
scope, on the chip where it is largest (profiler trace, ``scopes.py``)."""
from benchmarks.chip import scopes


def read(run):
    return scopes.scope_ms_max(run, "pipe.head")
