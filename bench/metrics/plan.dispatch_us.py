"""Mean host time of one plan call, dispatch included: the program's
``repro.plan.call`` spans in the program window's trace
(``bench/program_window.py``), which also writes its other numbers to
the run's notes.  Silent where the program has no such span."""
from bench import program_window


def read(run):
    window = program_window.measure(run)
    return None if window is None else window.get("plan.dispatch_us")
