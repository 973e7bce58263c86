"""Mean host milliseconds per step in the program's batch build: its
``data.build`` span around the numpy bigram chain, inside the traced
window (profiler trace, ``scopes.py``)."""
from benchmarks.chip import scopes


def read(run):
    r = scopes.of_run(run)
    if r is None or run.steps == 0 or "data.build" not in r["program_spans"]:
        return None
    return 1e3 * r["program_spans"]["data.build"] / run.steps
