"""The largest per-chip share of the traced window spent in the pipeline
rings' sends: the program's ``pipe.send`` scope, transfer and waiting for
the peer alike (profiler trace, ``scopes.py``)."""
from benchmarks.chip import scopes


def read(run):
    r = scopes.of_run(run)
    if r is None:
        return None
    s = [c.get("pipe.send") for c in r["scope_s_per_chip"]]
    if all(v is None for v in s):
        return None
    return 100.0 * max(v or 0.0 for v in s) / r["window_s"]
