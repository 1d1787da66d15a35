"""``aot_hit_share``: the share, in percent, of set-up's solve-program
builds that loaded their program from an export blob (utils/aot.py)
instead of tracing and lowering it: of the ``ksp.setup`` spans that carry
an ``aot`` attribute, at any depth, those with ``aot == "hit"``. The
other values are ``miss`` (a cold cache: the first call exported the
program), ``off`` (not exportable, or ``TPU_SOLVE_AOT=0``) and
``fallback`` (a loaded program re-traced). None where no span carries the
attribute: a program that does not record it. Moves setup_s."""


def _builds(tree, out):
    if tree["name"] == "ksp.setup" and "aot" in tree.get("attrs", {}):
        out.append(tree["attrs"]["aot"])
    for c in tree.get("children", ()):
        _builds(c, out)
    return out


def read(run):
    found = [a for s in run.setup_spans for a in _builds(s, [])]
    if not found:
        return None
    return 100.0 * sum(a == "hit" for a in found) / len(found)
