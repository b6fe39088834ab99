"""Share of the HBM roofline reached by the ops that compute the products.

Numerator: the least time to read, once, every row each worker computes in
the traced steps, at the configured float32 width and the chip's peak HBM
bandwidth. Denominator: the device time of every product op in the trace
(Pallas custom calls, dots, dot fusions), summed over the chips."""


def read(rec):
    trace = rec.get("trace")
    if not trace or trace["product_s"] <= 0 or not rec.get("traced_hbm_bytes"):
        return None
    least = rec["traced_hbm_bytes"] / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / trace["product_s"]
