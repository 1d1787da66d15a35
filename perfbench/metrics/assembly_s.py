"""``assembly_s``: seconds of set-up in host assembly: the outermost
``mat.assemble`` spans (core/mat.py ``Mat.from_csr``: CSR validation, the
ELL and DIA layouts, their one device placement). None where the program
records no such span: a matrix-free operator, or a program without the
span. Moves setup_s."""


def _outer(tree, name):
    if tree["name"] == name:
        return tree["t1"] - tree["t0"]
    return sum(_outer(c, name) for c in tree.get("children", ()))


def _found(tree, name):
    return tree["name"] == name or any(
        _found(c, name) for c in tree.get("children", ()))


def read(run):
    name = "mat.assemble"
    if not any(_found(s, name) for s in run.setup_spans):
        return None
    return sum(_outer(s, name) for s in run.setup_spans)
