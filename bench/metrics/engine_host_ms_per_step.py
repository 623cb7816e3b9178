"""Host work of the serving engine per step, fetch waits excluded.

The engine's own report (``PipelinedEngine.report()``): the loop's
steady-state seconds per step, less the host time blocked in the result
fetch.  What is left is every piece of host work a step costs: assembling
and policing the audio block, dispatch, admissions, vote or fire
bookkeeping.  Moves ``rt_streams``.
"""


def read(rec):
    r = rec.served["report"]
    if not r.get("steps"):
        return None
    per_step = r["steady_state_s"] * 1e3 / r["steps"]
    return per_step - r["host_blocked_ms_per_step"]["fetch"]
