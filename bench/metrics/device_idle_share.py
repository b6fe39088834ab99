"""Share of the traced window in which no op ran on the device, averaged
over the cell's chips."""


def read(rec):
    trace = rec.get("trace")
    if not trace or not trace["idle_share"]:
        return None
    return 100.0 * sum(trace["idle_share"]) / len(trace["idle_share"])
