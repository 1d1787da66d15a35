"""Unit tests for utils/hlo.py — the while-body reduce-site parser.

The parser gates the collective-volume tests (the 3/2/1 reduce-site
schedules of classic/guarded/pipelined CG) and the multi-chip dry-run's
one-reduce-site check, but until round 9 it had no direct unit
tests — a regression in the brace-matching walk would have surfaced as
an opaque schedule-gate failure three layers up.  These tests pin the
edge cases on hand-built StableHLO-shaped text (the textual contract
the module documents): programs with zero while-loops, nested while
bodies, multiple reduce dtypes in ONE stacked variadic all_reduce, and
the conditional-region exclusion.

Tests against REAL lowered programs keep the textual fixtures from
drifting from what jax actually prints, and pin the stencil schedules.
"""

import textwrap

import numpy as np
import pytest

from mpi_petsc4py_example_tpu.utils.hlo import (solver_loop_reduce_sites,
                                                while_body_reduce_sites)


def _hlo(body: str) -> str:
    return textwrap.dedent(body).strip("\n")


# ------------------------------------------------------- zero while-loops
def test_no_while_loops_yields_no_sites():
    text = _hlo("""
        module @jit_f {
          func.func public @main(%arg0: tensor<8xf64>) -> tensor<f64> {
            %0 = "stablehlo.all_reduce"(%arg0) ({
              ^bb0(%a: tensor<f64>, %b: tensor<f64>):
                %s = stablehlo.add %a, %b : tensor<f64>
                stablehlo.return %s : tensor<f64>
            }) : (tensor<8xf64>) -> tensor<f64>
            return %0 : tensor<f64>
          }
        }
    """)
    # a whole-program reduction OUTSIDE any loop is not a per-iteration
    # site: no while ops means no per-while counts at all
    assert while_body_reduce_sites(text) == []
    assert solver_loop_reduce_sites(text) == 0


def test_empty_program():
    assert while_body_reduce_sites("") == []
    assert solver_loop_reduce_sites("") == 0


# --------------------------------------------------------- basic counting
WHILE_TEMPLATE = """
    module @jit_solve {{
      func.func public @main(%arg0: tensor<8xf64>) -> tensor<8xf64> {{
        %w:2 = stablehlo.while(%iterArg = %arg0, %iterArg_0 = %c) : \
tensor<8xf64>, tensor<i32>
         cond {{
          %c0 = stablehlo.compare LT, %iterArg_0, %n : tensor<i1>
          stablehlo.return %c0 : tensor<i1>
        }} do {{
{body}
        }}
        return %w#0 : tensor<8xf64>
      }}
    }}
"""


def _while_program(body_lines):
    body = "\n".join(f"          {ln}" for ln in body_lines)
    return _hlo(WHILE_TEMPLATE.format(body=body))


def test_single_site_in_body():
    text = _while_program([
        '%r = "stablehlo.all_reduce"(%iterArg) ({',
        '  ^bb0(%a: tensor<f64>, %b: tensor<f64>):',
        '    %s = stablehlo.add %a, %b : tensor<f64>',
        '    stablehlo.return %s : tensor<f64>',
        '}) : (tensor<8xf64>) -> tensor<8xf64>',
        'stablehlo.return %r, %iterArg_0 : tensor<8xf64>, tensor<i32>',
    ])
    assert while_body_reduce_sites(text) == [1]
    assert solver_loop_reduce_sites(text) == 1


def test_stacked_psum_with_multiple_dtypes_is_one_site():
    """The krylov single-psum idiom: one VARIADIC all_reduce carrying
    several operands (stacked partial sums, possibly of different
    dtypes — f64 norms next to i32 convergence counters) is ONE reduce
    site, not len(operands)."""
    text = _while_program([
        '%r:3 = "stablehlo.all_reduce"(%p0, %p1, %p2) ({',
        '  ^bb0(%a: tensor<f64>, %b: tensor<f64>):',
        '    %s = stablehlo.add %a, %b : tensor<f64>',
        '    stablehlo.return %s : tensor<f64>',
        '}) : (tensor<4xf64>, tensor<4xf32>, tensor<i32>)'
        ' -> (tensor<4xf64>, tensor<4xf32>, tensor<i32>)',
        'stablehlo.return %r#0, %iterArg_0 : tensor<8xf64>, tensor<i32>',
    ])
    assert while_body_reduce_sites(text) == [1]


def test_two_psums_printed_on_one_line_are_two_sites():
    """Round-16 fix: the compact printer can emit TWO all_reduce defs on
    a single source line (stacked same-site reductions of DIFFERENT
    dtypes, where variadic stacking is illegal).  The old
    one-increment-per-line count conflated them into one site; the
    parser now counts distinct result defs per line."""
    inline = ('{ ^bb0(%a: tensor<f64>, %b: tensor<f64>): '
              '%s = stablehlo.add %a, %b : tensor<f64> '
              'stablehlo.return %s : tensor<f64> }')
    text = _while_program([
        f'%r0 = "stablehlo.all_reduce"(%p0) ({inline}) : '
        '(tensor<4xf64>) -> tensor<4xf64>  '
        f'%r1 = "stablehlo.all_reduce"(%p1) ({inline}) : '
        '(tensor<4xf32>) -> tensor<4xf32>',
        'stablehlo.return %r0, %iterArg_0 : tensor<8xf64>, tensor<i32>',
    ])
    assert while_body_reduce_sites(text) == [2]
    assert solver_loop_reduce_sites(text) == 2


def test_two_separate_sites_count_two():
    site = [
        '%r{i} = "stablehlo.all_reduce"(%p{i}) ({{',
        '  ^bb0(%a: tensor<f64>, %b: tensor<f64>):',
        '    %s = stablehlo.add %a, %b : tensor<f64>',
        '    stablehlo.return %s : tensor<f64>',
        '}}) : (tensor<8xf64>) -> tensor<8xf64>',
    ]
    lines = [ln.format(i=0) for ln in site] + \
            [ln.format(i=1) for ln in site] + \
            ['stablehlo.return %r1, %iterArg_0 : tensor<8xf64>, tensor<i32>']
    assert while_body_reduce_sites(_while_program(lines)) == [2]


# ----------------------------------------------------- nested while bodies
def test_nested_while_bodies():
    """An inner while inside the outer body: the inner op gets its own
    count, and the OUTER body's count includes the inner's sites (they
    do run once per outer iteration) — in program order, outer first."""
    inner = [
        '%inner:2 = stablehlo.while(%jArg = %x, %jArg_0 = %k) : '
        'tensor<8xf64>, tensor<i32>',
        ' cond {',
        '  %ic = stablehlo.compare LT, %jArg_0, %m : tensor<i1>',
        '  stablehlo.return %ic : tensor<i1>',
        '} do {',
        '  %ir = "stablehlo.all_reduce"(%jArg) ({',
        '    ^bb0(%a: tensor<f64>, %b: tensor<f64>):',
        '      %s = stablehlo.add %a, %b : tensor<f64>',
        '      stablehlo.return %s : tensor<f64>',
        '  }) : (tensor<8xf64>) -> tensor<8xf64>',
        '  stablehlo.return %ir, %jArg_0 : tensor<8xf64>, tensor<i32>',
        '}',
        'stablehlo.return %inner#0, %iterArg_0 : tensor<8xf64>, tensor<i32>',
    ]
    text = _while_program(inner)
    # program order: the outer while header appears first
    assert while_body_reduce_sites(text) == [1, 1]

    # solver_loop picks the LARGEST body — the outer loop here
    assert solver_loop_reduce_sites(text) == 1


def test_outer_body_with_own_site_plus_nested_loop():
    lines = [
        '%r0 = "stablehlo.all_reduce"(%p0) ({',
        '  ^bb0(%a: tensor<f64>, %b: tensor<f64>):',
        '    %s = stablehlo.add %a, %b : tensor<f64>',
        '    stablehlo.return %s : tensor<f64>',
        '}) : (tensor<8xf64>) -> tensor<8xf64>',
        '%inner:2 = stablehlo.while(%jArg = %r0, %jArg_0 = %k) : '
        'tensor<8xf64>, tensor<i32>',
        ' cond {',
        '  %ic = stablehlo.compare LT, %jArg_0, %m : tensor<i1>',
        '  stablehlo.return %ic : tensor<i1>',
        '} do {',
        '  %ir = "stablehlo.all_reduce"(%jArg) ({',
        '    ^bb0(%a: tensor<f64>, %b: tensor<f64>):',
        '      %s = stablehlo.add %a, %b : tensor<f64>',
        '      stablehlo.return %s : tensor<f64>',
        '  }) : (tensor<8xf64>) -> tensor<8xf64>',
        '  stablehlo.return %ir, %jArg_0 : tensor<8xf64>, tensor<i32>',
        '}',
        'stablehlo.return %inner#0, %iterArg_0 : tensor<8xf64>, tensor<i32>',
    ]
    text = _while_program(lines)
    assert while_body_reduce_sites(text) == [2, 1]
    # the outer (larger) body is the solver loop: 2 sites
    assert solver_loop_reduce_sites(text) == 2


# ------------------------------------------------- conditional exclusion
def _body_with_conditional_site():
    return [
        '%r0 = "stablehlo.all_reduce"(%p0) ({',
        '  ^bb0(%a: tensor<f64>, %b: tensor<f64>):',
        '    %s = stablehlo.add %a, %b : tensor<f64>',
        '    stablehlo.return %s : tensor<f64>',
        '}) : (tensor<8xf64>) -> tensor<8xf64>',
        '%c = "stablehlo.if"(%pred) ({',
        '  %cr = "stablehlo.all_reduce"(%p1) ({',
        '    ^bb0(%a: tensor<f64>, %b: tensor<f64>):',
        '      %s = stablehlo.add %a, %b : tensor<f64>',
        '      stablehlo.return %s : tensor<f64>',
        '  }) : (tensor<8xf64>) -> tensor<8xf64>',
        '  stablehlo.return %cr : tensor<8xf64>',
        '}, {',
        '  stablehlo.return %p1 : tensor<8xf64>',
        '}) : (tensor<i1>) -> tensor<8xf64>',
        'stablehlo.return %c, %iterArg_0 : tensor<8xf64>, tensor<i32>',
    ]


def test_conditional_sites_excluded_by_default():
    """The guard's every-N replacement verifier lives in a stablehlo.if
    branch — not a per-iteration cost, excluded from the schedule."""
    text = _while_program(_body_with_conditional_site())
    assert while_body_reduce_sites(text) == [1]


def test_conditional_sites_included_on_request():
    text = _while_program(_body_with_conditional_site())
    assert while_body_reduce_sites(text,
                                   exclude_conditionals=False) == [2]


# ------------------------------------------------- reduce-channel dtypes
_INLINE_REGION = ('{ ^bb0(%a: tensor<f64>, %b: tensor<f64>): '
                  '%s = stablehlo.add %a, %b : tensor<f64> '
                  'stablehlo.return %s : tensor<f64> }')


def test_reduce_site_dtypes_inline_region_then_multiline_op():
    """An all_reduce whose region opens AND closes on its header line:
    the old per-line brace count never saw the region open, so the scan
    ran forward to the NEXT op's closing line — reporting one site with
    the WRONG dtype and swallowing every all_reduce in between, which
    silently corrupted the TPC005 dtype gate and undercounted TPC007.
    Each site must report its own dtype, in lockstep with the site
    counter."""
    from mpi_petsc4py_example_tpu.utils.hlo import reduce_site_dtypes
    text = _while_program([
        f'%r0 = "stablehlo.all_reduce"(%p0) ({_INLINE_REGION}) : '
        '(tensor<4xf64>) -> tensor<4xf64>',
        '%r1 = "stablehlo.all_reduce"(%p1) ({',
        '  ^bb0(%a: tensor<f32>, %b: tensor<f32>):',
        '    %s = stablehlo.add %a, %b : tensor<f32>',
        '    stablehlo.return %s : tensor<f32>',
        '}) : (tensor<4xf32>) -> tensor<4xf32>',
        'stablehlo.return %r1, %iterArg_0 : tensor<8xf64>, tensor<i32>',
    ])
    assert reduce_site_dtypes(text) == [("f64",), ("f32",)]
    assert while_body_reduce_sites(text) == [2]


def test_reduce_site_dtypes_stacked_one_line_matches_site_count():
    """The stacked two-defs-on-one-line print shape (the round-16
    _line_reduce_defs fixture): one single-dtype tuple PER def, so
    total_reduce_sites (TPC007, len of this list) agrees with the
    while-body counter and TPC005 sees both dtypes — not [()] from
    parsing only the last `->` and discarding the rest."""
    from mpi_petsc4py_example_tpu.utils.hlo import reduce_site_dtypes
    text = _while_program([
        f'%r0 = "stablehlo.all_reduce"(%p0) ({_INLINE_REGION}) : '
        '(tensor<4xf64>) -> tensor<4xf64>  '
        f'%r1 = "stablehlo.all_reduce"(%p1) ({_INLINE_REGION}) : '
        '(tensor<4xf32>) -> tensor<4xf32>',
        'stablehlo.return %r0, %iterArg_0 : tensor<8xf64>, tensor<i32>',
    ])
    dtypes = reduce_site_dtypes(text)
    assert dtypes == [("f64",), ("f32",)]
    assert len(dtypes) == sum(while_body_reduce_sites(text))


def test_reduce_site_dtypes_variadic_is_one_tuple():
    """A variadic stacked psum is ONE site reporting one tuple with all
    its result dtypes — the single-psum krylov idiom."""
    from mpi_petsc4py_example_tpu.utils.hlo import reduce_site_dtypes
    text = _while_program([
        '%r:3 = "stablehlo.all_reduce"(%p0, %p1, %p2) ({',
        '  ^bb0(%a: tensor<f64>, %b: tensor<f64>):',
        '    %s = stablehlo.add %a, %b : tensor<f64>',
        '    stablehlo.return %s : tensor<f64>',
        '}) : (tensor<4xf64>, tensor<4xf32>, tensor<i32>)'
        ' -> (tensor<4xf64>, tensor<4xf32>, tensor<i32>)',
        'stablehlo.return %r#0, %iterArg_0 : tensor<8xf64>, tensor<i32>',
    ])
    assert reduce_site_dtypes(text) == [("f64", "f32", "i32")]


# ----------------------------------------------- against a real lowering
@pytest.mark.parametrize("nsites", [1, 2])
def test_parser_against_real_lowered_program(nsites):
    """The textual fixtures must not drift from what jax prints: lower a
    real single-device psum program and count its loop-body sites."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from mpi_petsc4py_example_tpu.parallel.mesh import DeviceComm

    comm = DeviceComm(devices=jax.devices()[:1])
    axis = comm.axis

    def local_fn(x):
        def body(carry):
            v, k = carry
            if nsites == 1:
                s = lax.psum(jnp.stack([jnp.sum(v), jnp.sum(v * 2)]), axis)
                v = v * s[0] + s[1]
            else:
                a = lax.psum(jnp.sum(v), axis)
                b = lax.psum(jnp.max(v), axis)
                v = v * a + b
            return (v, k + 1)

        return lax.while_loop(lambda c: c[1] < 5, body, (x, 0))[0]

    from jax.sharding import PartitionSpec as P
    fn = jax.jit(comm.shard_map(local_fn, (P(axis),), P(axis)))
    text = fn.lower(jnp.ones(8)).as_text()
    assert solver_loop_reduce_sites(text) == nsites


@pytest.mark.parametrize("ksp_type,sstep_s", [("pipecg", None),
                                               ("sstep", 2), ("sstep", 4),
                                               ("sstep", 8)])
def test_stencil_schedule_is_one_reduce_site(comm8, ksp_type, sstep_s):
    """The schedule gate on the sharded stencil operator: pipelined CG's
    loop body and each s-step block lower to ONE reduce site."""
    from mpi_petsc4py_example_tpu.contracts import lower_ksp
    text = lower_ksp(comm8, ksp_type=ksp_type, pc_type="jacobi",
                     operator="stencil", sstep_s=sstep_s)
    assert solver_loop_reduce_sites(text) == 1


# --------------------------------------- doubly-nested chains (megasolve)
def test_nested_chain_separates_outer_and_inner():
    """nested_loop_reduce_site_chain splits the fused program's schedule
    by depth: the outer body's OWN sites (nested while excluded) and the
    inner loop's sites — the flat largest-body count smears them."""
    from mpi_petsc4py_example_tpu.utils.hlo import (
        nested_loop_reduce_site_chain)
    lines = [
        '%r0 = "stablehlo.all_reduce"(%p0) ({',
        '  ^bb0(%a: tensor<f64>, %b: tensor<f64>):',
        '    %s = stablehlo.add %a, %b : tensor<f64>',
        '    stablehlo.return %s : tensor<f64>',
        '}) : (tensor<8xf64>) -> tensor<8xf64>',
        '%inner:2 = stablehlo.while(%jArg = %r0, %jArg_0 = %k) : '
        'tensor<8xf64>, tensor<i32>',
        ' cond {',
        '  %ic = stablehlo.compare LT, %jArg_0, %m : tensor<i1>',
        '  stablehlo.return %ic : tensor<i1>',
        '} do {',
        '  %ir = "stablehlo.all_reduce"(%jArg) ({',
        '    ^bb0(%a: tensor<f64>, %b: tensor<f64>):',
        '      %s = stablehlo.add %a, %b : tensor<f64>',
        '      stablehlo.return %s : tensor<f64>',
        '  }) : (tensor<8xf64>) -> tensor<8xf64>',
        '  %ir2 = "stablehlo.all_reduce"(%ir) ({',
        '    ^bb0(%a: tensor<f64>, %b: tensor<f64>):',
        '      %s = stablehlo.add %a, %b : tensor<f64>',
        '      stablehlo.return %s : tensor<f64>',
        '  }) : (tensor<8xf64>) -> tensor<8xf64>',
        '  stablehlo.return %ir2, %jArg_0 : tensor<8xf64>, tensor<i32>',
        '}',
        '%r1 = "stablehlo.all_reduce"(%inner#0) ({',
        '  ^bb0(%a: tensor<f64>, %b: tensor<f64>):',
        '    %s = stablehlo.add %a, %b : tensor<f64>',
        '    stablehlo.return %s : tensor<f64>',
        '}) : (tensor<8xf64>) -> tensor<8xf64>',
        'stablehlo.return %r1, %iterArg_0 : tensor<8xf64>, tensor<i32>',
    ]
    text = _while_program(lines)
    # outer own: r0 (inner-init stand-in) + r1 (exit gate stand-in);
    # inner: 2 per-iteration sites
    assert nested_loop_reduce_site_chain(text) == [2, 2]
    # the flat count on the same program includes the nested sites
    assert solver_loop_reduce_sites(text) == 4


def test_nested_chain_on_flat_program_is_one_element():
    from mpi_petsc4py_example_tpu.utils.hlo import (
        nested_loop_reduce_site_chain)
    lines = [
        '%ir = "stablehlo.all_reduce"(%iterArg) ({',
        '  ^bb0(%a: tensor<f64>, %b: tensor<f64>):',
        '    %s = stablehlo.add %a, %b : tensor<f64>',
        '    stablehlo.return %s : tensor<f64>',
        '}) : (tensor<8xf64>) -> tensor<8xf64>',
        'stablehlo.return %ir, %iterArg_0 : tensor<8xf64>, tensor<i32>',
    ]
    assert nested_loop_reduce_site_chain(_while_program(lines)) == [1]


def test_nested_chain_empty_program():
    from mpi_petsc4py_example_tpu.utils.hlo import (
        nested_loop_reduce_site_chain)
    assert nested_loop_reduce_site_chain("") == []
