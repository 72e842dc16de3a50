"""Device ms per window step of every op inside the hybrid layers' shared
transformer block (scope ``shared_block``: the concat with the
embeddings, both RMSNorms, attention, the MLP with its adapter and the
per-application ``linear``), averaged over the chips
(``trace_scoped.py``)."""


def read(run):
    t = run.trace_summary
    steps = run.counters.get("steps")
    if not t or not steps or "shared_block" not in t.get("within", {}):
        return None
    return 1e3 * t["within"]["shared_block"] / steps
