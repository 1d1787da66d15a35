"""``pc_setup_s``: seconds in the ``pc.setup`` spans (solvers/pc.py,
mg.py) during set-up. Moves setup_s."""


def _sum(tree, name):
    own = tree["t1"] - tree["t0"] if tree["name"] == name else 0.0
    return own + sum(_sum(c, name) for c in tree.get("children", ()))


def read(run):
    total = sum(_sum(s, "pc.setup") for s in run.setup_spans)
    return total if run.setup_spans else None
