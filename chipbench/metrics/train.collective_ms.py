"""Device ms per window step in collective ops (all-gather, reduce-scatter,
all-reduce, and the collective-permutes that carry split all-gathers and
reduce-scatters), averaged over the chips (``trace_scoped.py``)."""


def read(run):
    t = run.trace_summary
    steps = run.counters.get("steps")
    if not t or not steps or "collectives_s" not in t:
        return None
    return 1e3 * t["collectives_s"] / steps
