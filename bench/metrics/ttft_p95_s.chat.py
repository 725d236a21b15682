"""ttft_p95_s in the chat cell (layer: scheduler and batch core).

The 95th percentile of time to first token, from the due time.  At 0.12
requests a second a window holds six requests, so this tail is their
slowest and swings with when an arrival meets a long prefill step; it is
read here and not held to a bound end to end."""
import readers

LAYER = "scheduler and batch core"


def read(run):
    return readers.end_to_end(run, "ttft_p95_s")
