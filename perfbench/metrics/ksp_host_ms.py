"""``ksp_host_ms``: the KSP host path (solvers/ksp.py) in ms per solve:
each window ``ksp.solve`` root span less the ``ksp.fetch`` spans inside
it, where the host blocks on the device. Moves solve_s."""


def _fetch_s(tree):
    own = tree["t1"] - tree["t0"] if tree["name"] == "ksp.fetch" else 0.0
    return own + sum(_fetch_s(c) for c in tree.get("children", ())
                     if tree["name"] != "ksp.fetch")


def read(run):
    roots = [s for s in run.window_spans if s["name"] == "ksp.solve"]
    if not roots:
        return None
    host = [(s["t1"] - s["t0"]) - _fetch_s(s) for s in roots]
    return 1e3 * sum(host) / len(host)
