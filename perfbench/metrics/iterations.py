"""``iterations``: Krylov iterations per solve (solvers/ksp.py,
cg_plans.py, krylov.py), the mean over the window's solves as each KSP
result reports it (true-residual re-entries included). Moves solve_s."""


def read(run):
    its = [s["iterations"] for s in run.solves]
    return sum(its) / len(its) if its else None
