"""step_mfu in the chat cell (layer: fused step)."""
import readers

LAYER = "fused step"


def read(run):
    return readers.step_mfu(run)
