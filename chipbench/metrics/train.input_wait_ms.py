"""Host time per window step spent in ``next()`` on the batch iterator
that ``run_training`` consumes (the carousel's delivery)."""


def read(run):
    c = run.counters
    if "input_wait_s" not in c or not c.get("steps"):
        return None
    return 1e3 * c["input_wait_s"] / c["steps"]
