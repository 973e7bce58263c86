"""Device milliseconds per step of the optimizer's update of parameters
and state: the program's ``opt.update`` scope, on the chip where it is
largest (profiler trace, ``scopes.py``)."""
from benchmarks.chip import scopes


def read(run):
    return scopes.scope_ms_max(run, "opt.update")
