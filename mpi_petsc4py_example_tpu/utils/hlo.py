"""Lowered-StableHLO inspection helpers for the collective-schedule gates.

The collective-volume tests (tests/test_collective_volume.py), tpscheck's
contracts and ``dryrun_multichip``'s schedule gate count the reduce sites
INSIDE a solver loop's body — the per-iteration communication schedule the
pipelined/guarded/classic reduction plans pin (1 / 2 / 3 sites). Whole-
program ``all_reduce`` counts can't distinguish init/epilogue reductions
from per-iteration ones, so this module walks the pretty-printed
StableHLO's region structure instead.

Purely textual (brace matching on the ``stablehlo.while`` body region) —
no MLIR bindings required; the text shape is pinned by the jax version
the repo runs, and the tests exercising this parser fail loudly if a
version bump changes it.

Round 16 generalizes the module beyond reduce-site counting into the
parsing layer of the ``tpscheck`` contract verifier (tools/tpscheck):
per-site shape/dtype/byte extraction for any collective
(:func:`collective_sites`), reduce-channel dtype classification
(:func:`reduce_site_dtypes`), and donation/alias inspection of the
lowered entry point (:func:`donated_args`,
:func:`input_output_aliases`).
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass

#: StableHLO element-type -> bytes (the widths the byte gates price)
ELT_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2,
             "c64": 8, "c128": 16, "i32": 4, "i64": 8,
             "i1": 1, "i8": 1, "ui8": 1, "i16": 2, "ui32": 4}

#: ``%r = "stablehlo.all_reduce"`` / ``%r:3 = stablehlo.all_reduce`` —
#: one match per op DEFINITION, keyed by its result tuple, so stacked
#: psums printed on one line count as distinct sites
_REDUCE_DEF_RE = re.compile(
    r"(%[A-Za-z0-9_.$-]+(?::\d+)?)\s*=\s*\"?stablehlo\.all_reduce\b")

#: ``tensor<8x64xf32>`` / ``tensor<f64>`` — dims (possibly empty) + elt
_TENSOR_RE = re.compile(r"tensor<((?:\d+x)*)([a-z][a-z0-9]*)>")


def _body_region(lines, start):
    """Lines of the ``do { ... }`` region of the while op whose header is
    at ``lines[start]``, by brace counting from the ``do {`` opener."""
    depth = 0
    body: list[str] = []
    in_do = False
    for line in lines[start:]:
        if not in_do:
            # the cond region comes first; the body region opens at
            # '} do {' (the '}' closes the cond region — only braces
            # AFTER the 'do {' opener belong to the body's depth)
            if " do {" in line:
                in_do = True
                suf = line.split(" do {", 1)[1]
                depth = 1 + suf.count("{") - suf.count("}")
                if depth <= 0:
                    break
            continue
        depth += line.count("{") - line.count("}")
        if depth <= 0:
            break
        body.append(line)
    return body


def _count_sites(body_lines, exclude_conditionals=True) -> int:
    count = 0
    cond_depth = 0
    in_cond = False
    for bl in body_lines:
        if in_cond:
            cond_depth += bl.count("{") - bl.count("}")
            if cond_depth <= 0:
                in_cond = False
            continue
        if exclude_conditionals and ("stablehlo.if" in bl
                                     or "stablehlo.case" in bl):
            cond_depth = bl.count("{") - bl.count("}")
            in_cond = cond_depth > 0
            continue
        count += _line_reduce_defs(bl)
    return count


def _line_reduce_defs(line: str) -> int:
    """Number of DISTINCT ``all_reduce`` ops opening on one source line.

    Dedupes by result tuple: two stacked psums the printer emits on a
    single line (which happens for fused same-site reductions of
    DIFFERENT dtypes, where variadic stacking is illegal) are two
    sites, while the old one-increment-per-line counting conflated
    them into one. A line mentioning ``all_reduce`` with no parseable
    result definition (defensive: an unexpected print shape) still
    counts once rather than silently dropping the site.
    """
    defs = _REDUCE_DEF_RE.findall(line)
    if defs:
        return len(dict.fromkeys(defs))
    return 1 if "all_reduce" in line else 0


def while_body_reduce_sites(stablehlo_text: str,
                            exclude_conditionals: bool = True) -> list[int]:
    """Per-``stablehlo.while`` count of ``all_reduce`` sites in the LOOP
    BODY — the per-iteration reduce-site schedule.

    ``exclude_conditionals`` skips sites nested inside ``stablehlo.if`` /
    ``stablehlo.case`` regions of the body: the guard's periodic
    replacement verifier lives in an every-N conditional branch, which is
    not a per-iteration cost (the rr on/off volume gate pins that
    separately). Returns one count per while op, in program order.
    """
    lines = stablehlo_text.splitlines()
    return [_count_sites(_body_region(lines, i), exclude_conditionals)
            for i, line in enumerate(lines)
            if "stablehlo.while" in line]


def solver_loop_reduce_sites(stablehlo_text: str) -> int:
    """The reduce-site count of a solve program's MAIN loop: the while op
    with the largest body (the Krylov iteration — monitors/power
    iterations/helper loops are smaller in every program this gates).

    NOTE: the count INCLUDES sites inside nested while ops (a fused
    megasolve program's outer loop body contains the whole inner Krylov
    loop); use :func:`nested_loop_reduce_site_chain` to pin the
    per-depth schedules of doubly-nested programs.
    """
    lines = stablehlo_text.splitlines()
    best_len, best_sites = -1, 0
    for i, line in enumerate(lines):
        if "stablehlo.while" not in line:
            continue
        body = _body_region(lines, i)
        if len(body) > best_len:
            best_len, best_sites = len(body), _count_sites(body)
    return best_sites


# ---------------------------------------------------------------------------
# doubly-nested while bodies (fused megasolve programs): the outer
# refinement loop wraps the inner Krylov loop, so per-depth schedules
# need nested-region-aware counting
# ---------------------------------------------------------------------------


def _nested_while_spans(body_lines) -> list[tuple[int, int]]:
    """Half-open ``(start, end)`` line-index ranges of every top-level
    nested ``stablehlo.while`` OP inside a body-region line list — the
    whole op, cond and do regions both, by brace counting from the
    header line."""
    spans = []
    i = 0
    while i < len(body_lines):
        if "stablehlo.while" not in body_lines[i]:
            i += 1
            continue
        depth = 0
        opened = False
        j = i
        while j < len(body_lines):
            depth += (body_lines[j].count("{")
                      - body_lines[j].count("}"))
            if depth > 0:
                opened = True
            if opened and depth <= 0:
                break
            j += 1
        spans.append((i, min(j + 1, len(body_lines))))
        i = spans[-1][1]
    return spans


def _own_sites(body_lines, exclude_conditionals=True) -> int:
    """Reduce sites of a loop body EXCLUDING nested while regions — the
    body's own per-iteration schedule."""
    spans = _nested_while_spans(body_lines)
    skip = set()
    for a, b in spans:
        skip.update(range(a, b))
    kept = [ln for idx, ln in enumerate(body_lines) if idx not in skip]
    return _count_sites(kept, exclude_conditionals)


def nested_loop_reduce_site_chain(stablehlo_text: str,
                                  exclude_conditionals: bool = True
                                  ) -> list[int]:
    """Per-depth OWN reduce-site counts along the largest-body while
    chain of a lowered program.

    Element 0 is the outermost solver loop's own schedule (sites per
    outer iteration, nested loops excluded), element 1 its largest
    nested while's own schedule, and so on. A fused megasolve program
    reports ``[outer refinement sites, inner Krylov sites]`` — the
    collective-volume gates pin element 1 at the 3/2/1 schedules the
    unfused programs honor (the fusion must not change the inner loop's
    per-iteration communication), and element 0 at the outer recurrence's
    fixed cost (the inner init reductions + the fp64 exit-gate psum).
    Unfused (singly-nested) programs report a one-element chain.
    """
    lines = stablehlo_text.splitlines()
    best_len, best_body = -1, []
    for i, line in enumerate(lines):
        if "stablehlo.while" not in line:
            continue
        body = _body_region(lines, i)
        if len(body) > best_len:
            best_len, best_body = len(body), body
    if best_len < 0:
        return []
    chain = []
    body = best_body
    while True:
        chain.append(_own_sites(body, exclude_conditionals))
        spans = _nested_while_spans(body)
        if not spans:
            return chain
        a, b = max(spans, key=lambda s: s[1] - s[0])
        body = _body_region(body[a:b], 0)


# ---------------------------------------------------------------------------
# collective-site classification (tpscheck's measurement layer): per-site
# result shape / element type / byte volume for any collective op, plus
# donation/alias inspection of the lowered entry point
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CollectiveSite:
    """One collective op site in a lowered program: the op kind, the
    result shape, and the element type — enough to price its bytes."""

    op: str                  # "all_gather" | "collective_permute" | ...
    dims: tuple              # result tensor dims, () for scalars
    elt: str                 # StableHLO element type, e.g. "f32"

    @property
    def elems(self) -> int:
        return math.prod(self.dims) if self.dims else 1

    @property
    def bytes(self) -> int:
        return self.elems * ELT_BYTES.get(self.elt, 0)


def collective_sites(stablehlo_text: str, op_name: str
                     ) -> list[CollectiveSite]:
    """Every ``stablehlo.<op_name>`` site in the program, with result
    shape and element type parsed from the LAST ``tensor<...>`` on the
    op's header line (the result type — operand types precede it).

    Works for the single-line collectives (``all_gather``,
    ``collective_permute``); use :func:`reduce_site_dtypes` for the
    region-carrying ``all_reduce``, whose result types print on its
    CLOSING line instead.
    """
    needle = f"stablehlo.{op_name}"
    sites = []
    for line in stablehlo_text.splitlines():
        if needle not in line:
            continue
        matches = _TENSOR_RE.findall(line)
        if not matches:
            continue
        dims_s, elt = matches[-1]
        dims = tuple(int(d) for d in dims_s.split("x") if d)
        sites.append(CollectiveSite(op=op_name, dims=dims, elt=elt))
    return sites


def _reduce_region_close(lines, start: int) -> int:
    """Index of the line on which the region(s) of the ``all_reduce``
    op(s) opening at ``lines[start]`` close — CHARACTER-level brace
    tracking, so a region that opens and closes on its header line (the
    compact printer's inline shape) resolves to ``start`` itself.  The
    old per-line net count (``count('{') - count('}')``) never saw such
    a region open and scanned forward into the NEXT op's closing line,
    attributing that op's result dtype to the inline site and skipping
    every all_reduce in between."""
    depth = 0
    opened = False
    for j in range(start, len(lines)):
        for ch in lines[j]:
            if ch == "{":
                depth += 1
                opened = True
            elif ch == "}":
                depth -= 1
        if opened and depth <= 0:
            return j
    return len(lines) - 1


def _tail_elts(text: str) -> tuple:
    """Result element types from the portion of ``text`` after its last
    ``->`` (the result type of a ``}) : (...) -> ...`` trailer)."""
    tail = text.rsplit("->", 1)[-1] if "->" in text else text
    return tuple(elt for _dims, elt in _TENSOR_RE.findall(tail))


def reduce_site_dtypes(stablehlo_text: str) -> list[tuple[str, ...]]:
    """Per-``all_reduce``-site result element types, one tuple per site
    in program order (variadic stacked reductions report one tuple with
    several entries).

    ``all_reduce`` carries a region, so its result types print on the
    op's CLOSING ``}) : (...) -> ...`` line — the header line itself
    when the printer emits the region inline, including the stacked
    several-defs-on-one-line shape, where each def's types come from
    its own line segment so the site list stays in lockstep with
    :func:`_line_reduce_defs`.  The reduce-channel dtype contracts pin
    these: a plan whose fp64 exit-gate psum silently becomes f32
    changes the convergence semantics without changing any site count.
    """
    lines = stablehlo_text.splitlines()
    out: list[tuple[str, ...]] = []
    i = 0
    while i < len(lines):
        n_defs = _line_reduce_defs(lines[i])
        if not n_defs:
            i += 1
            continue
        if "{" not in lines[i]:
            # defensive: a region-less mention can't anchor a brace
            # scan — read what types the line itself offers
            out.append(_tail_elts(lines[i]))
            i += 1
            continue
        j = _reduce_region_close(lines, i)
        if j == i:
            # fully inline op(s): result types live on the header line,
            # one `}) : (...) -> type` trailer per def — parse each
            # def's own segment so stacked same-line psums of different
            # dtypes report one tuple each
            starts = [m.start()
                      for m in _REDUCE_DEF_RE.finditer(lines[i])]
            if starts:
                bounds = starts[1:] + [len(lines[i])]
                out.extend(_tail_elts(lines[i][a:b])
                           for a, b in zip(starts, bounds))
            else:       # defensive print shape with no parseable def
                out.append(_tail_elts(lines[i]))
        else:
            elts = _tail_elts(lines[j])
            if n_defs > 1 and len(elts) == n_defs:
                # stacked same-line ops: one single-result tuple each
                out.extend((e,) for e in elts)
            else:
                out.append(elts)
        i = j + 1
    return out


def _main_signature(stablehlo_text: str) -> str:
    """The argument list of the ``@main`` entry point, paren-matched
    from ``@main(`` (signatures can span lines). Empty when absent."""
    idx = stablehlo_text.find("@main(")
    if idx < 0:
        return ""
    start = idx + len("@main(")
    depth = 1
    for pos in range(start, len(stablehlo_text)):
        ch = stablehlo_text[pos]
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return stablehlo_text[start:pos]
    return stablehlo_text[start:]


_ARG_SPLIT_RE = re.compile(r"%arg(\d+):")


def _main_arg_attrs(stablehlo_text: str) -> dict[int, str]:
    """Per-argument attribute text of the ``@main`` signature (the
    ``{...}`` trailing each ``%argN: tensor<...>`` declaration)."""
    sig = _main_signature(stablehlo_text)
    if not sig:
        return {}
    parts = _ARG_SPLIT_RE.split(sig)
    # parts = [prefix, idx0, decl0, idx1, decl1, ...]
    out = {}
    for k in range(1, len(parts) - 1, 2):
        out[int(parts[k])] = parts[k + 1]
    if len(parts) % 2 == 0:        # trailing idx with no decl text
        out[int(parts[-1])] = ""
    return out


def donated_args(stablehlo_text: str) -> tuple[int, ...]:
    """Indices of ``@main`` arguments marked ``jax.buffer_donor = true``
    — buffers jax may reuse for outputs (donation requested but not yet
    bound to a specific output)."""
    return tuple(sorted(
        i for i, attrs in _main_arg_attrs(stablehlo_text).items()
        if "jax.buffer_donor = true" in attrs))


def input_output_aliases(stablehlo_text: str) -> dict[int, int]:
    """``{arg_index: output_index}`` for ``@main`` arguments carrying a
    ``tf.aliasing_output`` attribute — donations XLA has committed to
    alias onto a specific result. A donated solve program losing its
    alias silently doubles its residency; the donation contracts pin
    this."""
    out = {}
    for i, attrs in _main_arg_attrs(stablehlo_text).items():
        m = re.search(r"tf\.aliasing_output\s*=\s*(\d+)", attrs)
        if m:
            out[i] = int(m.group(1))
    return out
