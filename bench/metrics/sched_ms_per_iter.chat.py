"""sched_ms_per_iter in the chat cell (layer: scheduler and batch core)."""
import readers

LAYER = "scheduler and batch core"


def read(run):
    return readers.sched_ms_per_iter(run)
