"""Host time per step: the window's wall time not spent inside the
runner's timed dispatch (put, execute, block), over the steps."""


def read(rec):
    if not rec.get("steps"):
        return None
    return 1e3 * rec["host_s"] / rec["steps"]
