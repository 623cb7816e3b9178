"""Host time blocked in the result fetch per step (engine report).

The fetch is where the host waits for the device and copies a step's
results back; it is the part of a decision's latency the host does not
spend working.  Moves ``decision_p99_ms``.
"""


def read(rec):
    r = rec.served["report"]
    if not r.get("steps"):
        return None
    return r["host_blocked_ms_per_step"]["fetch"]
