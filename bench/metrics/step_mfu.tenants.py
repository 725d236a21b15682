"""step_mfu in the tenants cell (layer: fused step)."""
import readers

LAYER = "fused step"


def read(run):
    return readers.step_mfu(run)
