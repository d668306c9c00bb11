"""Device time of the prefill and page-scatter programs per prefilled
request in the traced window (whole-prompt prefill: one of each)."""
from readings import prefill_ms_per_req


def read(ctx):
    return prefill_ms_per_req(ctx)
