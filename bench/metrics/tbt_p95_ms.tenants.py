"""tbt_p95_ms in the tenants cell (layer: engine iteration and device
boundary): the 95th percentile of the gaps between a request's tokens,
which above capacity is the length of a full mixed iteration."""
import readers

LAYER = "engine iteration and device boundary"


def read(run):
    return readers.end_to_end(run, "tbt_p95_ms")
