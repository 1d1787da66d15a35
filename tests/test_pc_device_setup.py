"""Device-side bjacobi block inversion (``-pc_setup_device``).

The round-4 cfg4 artifact bills ``pc_setup_s`` 17.5 s to a single-core host
LAPACK sweep over 32 dense 2048² block inverses; the device path ships the
raw blocks instead (same bytes) and inverts them as one batched MXU LU +
Newton polish. These tests force the device path on the simulated CPU mesh
(where 'auto' correctly stays on host) and pin:

* numerical agreement with the host fp64-factorize-then-cast path,
* end-to-end solves through a device-built PC,
* the quality-gate fallback for singular blocks,
* the 'auto' placement rule and option plumbing.
"""

import numpy as np
import pytest
import scipy.sparse as sp

import mpi_petsc4py_example_tpu as tps
from mpi_petsc4py_example_tpu.solvers import pc as pcmod

from test_ksp import convdiff2d, manufactured, solve


def _blocks_of(pc_obj):
    """Host copy of the built (M, bs, bs) inverse stack."""
    return np.asarray(pc_obj._arrays[0])


def _built_bjacobi(comm, A, dtype, setup_device, blocks=0, ell=True):
    M = tps.Mat.from_scipy(comm, sp.csr_matrix(A, dtype=dtype))
    if not ell:
        M.ell_cols = None     # no device-resident ELL: host block extraction
    p = tps.PC(comm)
    p.set_type("bjacobi")
    p.bjacobi_blocks = blocks
    p.setup_device = setup_device
    p.set_up(M)
    return p


class TestDeviceInverseBlocks:
    @pytest.mark.parametrize("dtype", [np.float32, np.float64])
    def test_matches_host_path(self, comm8, dtype):
        A = convdiff2d(16)          # n=256 -> 32 rows/device
        ph = _built_bjacobi(comm8, A, dtype, "0")
        pd = _built_bjacobi(comm8, A, dtype, "1")
        ih, idv = _blocks_of(ph), _blocks_of(pd)
        assert ih.shape == idv.shape and ih.dtype == idv.dtype
        tol = 2e-5 if dtype == np.float32 else 1e-12
        np.testing.assert_allclose(idv, ih, rtol=tol, atol=tol)

    def test_ell_diag_blocks_matches_host_extraction(self, comm8):
        """Device ELL block extraction == host CSR block extraction
        (including off-block masking and identity padding)."""
        A = convdiff2d(16)
        M = tps.Mat.from_scipy(comm8, A)
        n = A.shape[0]
        bs = M.ell_cols.shape[0] // 8
        dev = np.asarray(pcmod._ell_diag_blocks(M.ell_cols, M.ell_vals,
                                                bs, n))
        host = pcmod._dense_diag_blocks(A.tocsr(), n, bs, 8, np.float64)
        np.testing.assert_allclose(dev, host, rtol=0, atol=0)

    def test_identity_padding_rows(self, comm8):
        # n=60 over 8 devices -> lsize 8, last device half padding: the
        # padded slots must invert to identity exactly (pass-through)
        A = sp.diags(np.linspace(2.0, 3.0, 60)).tocsr()
        pd = _built_bjacobi(comm8, A, np.float64, "1")
        inv = _blocks_of(pd)
        # device 7 rows 56..59 real, 60..63 identity-padded
        np.testing.assert_allclose(np.diag(inv[7])[4:], 1.0, rtol=1e-12)

    def test_singular_block_falls_back_to_none(self, comm8):
        blocks = np.stack([np.eye(4)] * 8)
        blocks[3, 2, 2] = 0.0       # exactly singular block
        blocks[3, 2, :] = 0.0
        out = pcmod._device_inverse_blocks(tps.DeviceComm(), blocks)
        assert out is None

    def test_ill_conditioned_gate(self, comm8):
        # fp32 inversion of a cond ~1e9 block cannot pass the 1e-2 gate
        d = np.ones(4, np.float32)
        d[0] = 1e-9
        blocks = np.stack([np.diag(d)] * 8).astype(np.float32)
        # diagonal matrices invert exactly even in fp32 — perturb off-diag
        rng = np.random.default_rng(0)
        blocks += 1e-5 * rng.standard_normal(blocks.shape).astype(np.float32)
        out = pcmod._device_inverse_blocks(tps.DeviceComm(), blocks)
        # either rejected (None) or genuinely accurate — never a silently
        # bad inverse
        if out is not None:
            B, X = blocks, np.asarray(out)
            q = np.max(np.abs(np.eye(4) - np.einsum("bij,bjk->bik", B, X)))
            assert q <= pcmod._DEVICE_INV_GATE


class TestEndToEnd:
    @pytest.mark.parametrize("dtype", [np.float32, np.float64])
    def test_bcgs_bjacobi_device_setup(self, comm8, dtype):
        """cfg4's shape: unsymmetric conv-diff, BCGS solved through a
        PC whose block inverses were built ON the mesh devices."""
        A = sp.csr_matrix(convdiff2d(16), dtype=dtype)
        x_true, b = manufactured(A)
        rtol = 1e-5 if dtype == np.float32 else 1e-10
        x, res, ksp = solve(comm8, A, b.astype(dtype), "bcgs", "bjacobi",
                            rtol=rtol)
        pc = ksp.get_pc()
        pc.setup_device = "1"             # rebuild via the device path...
        ksp.set_up()
        assert pc.setup_mode == "device"  # ...and prove it engaged
        M = ksp.get_operators()[0]
        x2, b2 = M.get_vecs()
        b2.set_global(b.astype(dtype))
        res2 = ksp.solve(b2, x2)          # solve THROUGH the device-built PC
        assert res.converged and res2.converged
        np.testing.assert_allclose(x2.to_numpy(), x_true, rtol=100 * rtol,
                                   atol=100 * rtol)

    def test_multi_block_split(self, comm8):
        """-pc_bjacobi_blocks with the device path (batched M > ndev)."""
        A = convdiff2d(16)          # lsize 32 -> 4 blocks of 8 per device
        x_true, b = manufactured(A)
        ph = _built_bjacobi(comm8, A, np.float64, "0", blocks=32)
        pd = _built_bjacobi(comm8, A, np.float64, "1", blocks=32)
        np.testing.assert_allclose(_blocks_of(pd), _blocks_of(ph),
                                   rtol=1e-12, atol=1e-12)


class TestDenseLU:
    @pytest.mark.parametrize("dtype", [np.float32, np.float64])
    def test_dense_lu_device_matches_host(self, comm8, dtype):
        """The MUMPS-slot dense path: device-built padded inverse equals
        the host LAPACK one (including the zeroed pad block)."""
        A = sp.csr_matrix(convdiff2d(7), dtype=dtype)     # n=49, pads to 56
        M = tps.Mat.from_scipy(comm8, A, dtype=dtype)
        invs = {}
        for sd in ("0", "1"):
            p = tps.PC(comm8)
            p.set_type("lu")
            p.setup_device = sd
            p.set_up(M)
            assert p._factor_mode == "dense"
            invs[sd] = np.asarray(p._arrays[0])
        assert invs["1"].shape == invs["0"].shape
        n = A.shape[0]
        # pad block must be exactly zero (host convention)
        assert not invs["1"][n:, :].any() and not invs["1"][:, n:].any()
        tol = 2e-5 if dtype == np.float32 else 1e-10
        np.testing.assert_allclose(invs["1"], invs["0"], rtol=tol, atol=tol)

    def test_preonly_solve_through_device_dense_lu(self, comm8):
        A = sp.csr_matrix(convdiff2d(7), dtype=np.float64)
        rng = np.random.default_rng(3)
        x_true = rng.random(A.shape[0])
        b = A @ x_true
        M = tps.Mat.from_scipy(comm8, A)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("preonly")
        pc = ksp.get_pc()
        pc.set_type("lu")
        pc.setup_device = "1"
        ksp.set_up()
        assert pc.setup_mode == "device"
        x, bv = M.get_vecs()
        bv.set_global(b)
        ksp.solve(bv, x)
        rr = np.linalg.norm(b - A @ x.to_numpy()) / np.linalg.norm(b)
        assert rr <= 1e-12, rr


class TestSeededPolish:
    def test_seeded_matches_native_to_f64_floor(self, comm8):
        """The F32-LU-seeded f64 polish reaches the same quality band as
        a native f64 LU for moderately conditioned blocks."""
        rng = np.random.default_rng(0)
        B = rng.random((8, 32, 32)) + 4 * np.eye(32)
        Xn, qn = pcmod._inv_polish(B)
        Xs, qs = pcmod._inv_polish_seeded(B)
        assert float(qn) < 1e-12 and float(qs) < 1e-11
        np.testing.assert_allclose(np.asarray(Xs), np.asarray(Xn),
                                   rtol=1e-9, atol=1e-9)


class TestGateFallback:
    def test_gate_failure_reuses_extracted_stack(self, comm8, monkeypatch):
        """A rejected device inversion after HOST block extraction falls
        back to LAPACK over the already-extracted dense stack — same
        numbers as the pure host path, setup_mode 'host'. (ELL extraction
        is unavailable so the host-extract + dense-reuse branch is the one
        under test.)"""
        monkeypatch.setattr(pcmod, "_device_inverse_blocks",
                            lambda comm, blocks: None)
        A = convdiff2d(16)
        ph = _built_bjacobi(comm8, A, np.float64, "0")
        pf = _built_bjacobi(comm8, A, np.float64, "1", ell=False)  # rejected
        assert pf.setup_mode == "host"
        np.testing.assert_allclose(_blocks_of(pf), _blocks_of(ph),
                                   rtol=1e-12, atol=1e-12)

    def test_gate_failure_after_ell_extraction(self, comm8, monkeypatch):
        """Same rejection with the ELL extraction route: falls back to the
        host CSR path and still matches."""
        monkeypatch.setattr(pcmod, "_device_inverse_blocks",
                            lambda comm, blocks: None)
        A = convdiff2d(16)
        ph = _built_bjacobi(comm8, A, np.float64, "0")
        pf = _built_bjacobi(comm8, A, np.float64, "1")
        assert pf.setup_mode == "host"
        np.testing.assert_allclose(_blocks_of(pf), _blocks_of(ph),
                                   rtol=1e-12, atol=1e-12)

    def test_gate_rejection_is_counted(self, comm8):
        """A singular block fails the device quality gate; the rejection
        is counted in ``gate_fallbacks`` before the host path runs."""
        d = np.ones(64)
        d[10] = 0.0
        before = pcmod.gate_fallbacks["block"]
        with pytest.raises(Exception, match="[Ss]ingular"):
            _built_bjacobi(comm8, sp.diags(d).tocsr(), np.float64, "1")
        assert pcmod.gate_fallbacks["block"] == before + 1

    def test_device_error_propagates(self, comm8, monkeypatch):
        """A compile/runtime error of the device inversion is raised, not
        hidden behind the host LAPACK path."""
        def boom(B):
            raise RuntimeError("forced: device program failed")

        monkeypatch.setattr(pcmod, "_inv_polish", boom)
        with pytest.raises(RuntimeError, match="forced"):
            _built_bjacobi(comm8, convdiff2d(16), np.float64, "1")

    def test_singular_block_raises_proper_error(self, comm8):
        """End-to-end: device gate rejects a singular block and the host
        fallback raises LAPACK's singular-matrix error (not a silent bad
        inverse)."""
        d = np.ones(64)
        d[10] = 0.0
        A = sp.diags(d).tocsr()
        with pytest.raises(Exception, match="[Ss]ingular"):
            _built_bjacobi(comm8, A, np.float64, "1")


class TestPlacementRule:
    def test_auto_is_host_on_cpu_mesh(self, comm8):
        assert not pcmod._want_device_setup(comm8, np.float32, "auto")
        assert not pcmod._want_device_setup(comm8, np.float64, "auto")

    def test_f64_ok_widens_auto_only_with_flag(self, comm8):
        # the BPCR path passes f64_ok=True (f32-LU seed + emulated-f64
        # polish); bjacobi does not — but neither engages on a CPU mesh
        assert not pcmod._want_device_setup(comm8, np.float64, "auto",
                                            f64_ok=True)
        assert not pcmod._want_device_setup(comm8, np.complex128, "auto",
                                            f64_ok=True)

    def test_forced_values(self, comm8):
        assert pcmod._want_device_setup(comm8, np.float64, "1")
        assert pcmod._want_device_setup(comm8, np.float64, "device")
        assert not pcmod._want_device_setup(comm8, np.float32, "0")
        with pytest.raises(ValueError, match="pc_setup_device"):
            pcmod._want_device_setup(comm8, np.float32, "maybe")

    def test_option_plumbing(self, comm8):
        tps.global_options().parse_argv(["prog", "-pc_setup_device", "1"])
        A = convdiff2d(8)
        M = tps.Mat.from_scipy(comm8, A)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.get_pc().set_type("bjacobi")
        ksp.set_from_options()
        assert ksp.get_pc().setup_device == "1"

    def test_tunables_key_rebuilds(self, comm8):
        """Flipping setup_device must invalidate the built arrays."""
        A = convdiff2d(8)
        p = _built_bjacobi(comm8, A, np.float64, "0")
        key0 = p._built_for
        p.setup_device = "1"
        p.set_up(p._mat)
        assert p._built_for != key0
