"""Session device functions: device time per execution of the step program
(``step_windowed``), from the trace (ms)."""


def read(run):
    prog = ((run["trace"] or {}).get("programs") or {}).get("step_windowed")
    if not prog or not prog["count"]:
        return None
    return 1e3 * prog["seconds"] / prog["count"]
