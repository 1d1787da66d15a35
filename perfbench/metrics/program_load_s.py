"""``program_load_s``: program build and compile-cache load in set-up's
first solve (``ksp.setup``, utils/aot.py). The solve program is a
``jax.jit`` that traces, lowers and loads from the compile cache at its
first call, inside ``ksp.dispatch``; so this is the first ``ksp.solve``'s
``ksp.setup`` spans less their ``pc.setup`` children, plus its
``ksp.dispatch`` spans. Moves setup_s."""


def _walk(tree, out):
    out.append(tree)
    for c in tree.get("children", ()):
        _walk(c, out)
    return out


def read(run):
    roots = [s for s in run.setup_spans if s["name"] == "ksp.solve"]
    if not roots:
        return None
    total = 0.0
    for sp in _walk(roots[0], []):
        if sp["name"] == "ksp.setup":
            total += sp["t1"] - sp["t0"] - sum(
                c["t1"] - c["t0"] for c in sp.get("children", ())
                if c["name"] == "pc.setup")
        elif sp["name"] == "ksp.dispatch":
            total += sp["t1"] - sp["t0"]
    return total
