"""light_ttft_p95_s in the tenants cell (layer: scheduler and batch core).

The 95th percentile of the light clients' time to first token, from the
due time.  Above capacity this tail swings with the smallest change in
when an arrival meets an iteration, so it is read here and not held to
a bound end to end."""
import readers

LAYER = "scheduler and batch core"


def read(run):
    return readers.end_to_end(run, "ttft_p95_s")
