"""First-arrival combine time per step: the runner's ``combine_s`` (the
partials' fetch, the include refresh and the winner gather, on the host
clock), summed over the window's steps, over the steps. None where the
program reports no such time."""


def read(rec):
    if rec.get("combine_s") is None or not rec.get("steps"):
        return None
    return 1e3 * rec["combine_s"] / rec["steps"]
