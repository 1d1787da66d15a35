"""``compile_load_s``: seconds of set-up in XLA compiles or
persistent-cache loads: the union of set-up's ``compile.backend`` spans
(telemetry/compile_events.py; each says ``cache_hit``), over every
program set-up builds. None where the program records no compile spans.
Moves setup_s.

Also the home of the interval union that trace_lower_s.py and
setup_unspanned_s.py load from here."""

NAMES = ("compile.backend",)


def _walk(tree, names, out):
    if tree["name"] in names:
        out.append((tree["t0"], tree["t1"]))
    for c in tree.get("children", ()):
        _walk(c, names, out)
    return out


def union_s(intervals):
    """Seconds covered by ``(t0, t1)`` intervals, an overlap once."""
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > end:
            total += e - max(s, end)
            end = e
    return total


def seconds_in(run, names):
    """The union of set-up's spans named ``names``, at any depth; None
    where there are none."""
    found = [iv for s in run.setup_spans for iv in _walk(s, names, [])]
    return union_s(found) if found else None


def read(run):
    return seconds_in(run, NAMES)
