"""Planner time per churn event: the runner's ``replan_s`` (event,
estimator ingest, plan adoption, include refresh) of each first step
after an event in the window, summed, over the events."""


def read(rec):
    times = rec.get("event_replan_s")
    if not times:
        return None
    return 1e3 * sum(times) / len(times)
