"""paged_attn_roofline in the chat cell (layer: paged attention kernel)."""
import readers

LAYER = "paged attention kernel"


def read(run):
    return readers.paged_attn_roofline(run)
