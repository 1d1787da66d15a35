"""``pallas_share_pct``: the share of device busy time spent in Pallas
kernels (``tpu_custom_call``, ops/pallas_stencil.py), from the trace.
Moves solve_s."""


def read(run):
    red = run.trace
    if not red or red["busy_s"] <= 0:
        return None
    return 100.0 * red["category_s"].get("pallas", 0.0) / red["busy_s"]
