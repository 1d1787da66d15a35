"""Problem generators mirroring the reference drivers' model families.

* :func:`random_system` — the manufactured-solution system of ``test.py:12-17``
  (seeded scipy.sparse.random, exact X, B = A·X).
* :func:`tridiag_family` — the symmetric tridiagonal family of
  ``test2.py:6-18`` (band values i+j+1), built vectorized rather than via the
  reference's dense double loop.
* :func:`convdiff2d` — unsymmetric convection-diffusion (BASELINE config 4).
"""

from __future__ import annotations

import numpy as np

# scipy.sparse is imported inside each builder: it costs driver start-up
# time, and the drivers that never touch a CSR oracle shouldn't pay it


def random_system(n: int = 100, seed: int = 42, density: float = 0.1):
    """Seeded random CSR system with manufactured solution: A, X, B=A·X."""
    import scipy.sparse as sp
    rng = np.random.default_rng(seed=seed)
    A = sp.random(n, n, density=density, format="csr", dtype=np.float64,
                  random_state=rng)
    X = rng.random(n)
    B = A.dot(X)
    return A, X, B


def tridiag_family(n: int = 100) -> "sp.csr_matrix":
    """Symmetric tridiagonal matrix with A[i,j] = i+j+1 on the band."""
    import scipy.sparse as sp
    i = np.arange(n)
    main = 2.0 * i + 1.0
    off = i[:-1] + i[1:] + 1.0
    return sp.diags([off, main, off], [-1, 0, 1], format="csr")


def convdiff2d(nx: int, ny: int | None = None,
               beta: float = 0.3) -> "sp.csr_matrix":
    """2D convection-diffusion: 5-point Laplacian + first-order convection.

    ``beta`` is the convection strength (cell Péclet/2); nonzero beta makes
    the operator unsymmetric, exercising GMRES/BiCGStab.
    """
    import scipy.sparse as sp
    ny = ny or nx
    n = nx * ny
    idx = np.arange(n)
    x = idx % nx
    diags = {0: 4.0 * np.ones(n)}
    east = np.where(x[:-1] + 1 < nx, -1.0 + beta, 0.0)
    west = np.where(x[1:] - 1 >= 0, -1.0 - beta, 0.0)
    north = -np.ones(n - nx)
    south = -np.ones(n - nx)
    return sp.diags([west, diags[0], east, south, north],
                    [-1, 0, 1, -nx, nx], format="csr")
