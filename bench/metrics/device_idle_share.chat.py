"""device_idle_share in the chat cell (layer: engine iteration and device boundary)."""
import readers

LAYER = "engine iteration and device boundary"


def read(run):
    return readers.device_idle_share(run)
