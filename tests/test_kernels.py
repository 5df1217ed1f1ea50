"""Pallas kernel validation (interpret=True on CPU): shape/dtype sweeps
against the pure-jnp oracles in each kernel's ref.py."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sample_problem, solve_joint_optimal


# ------------------------------------------------------------- selection

class TestSelectionSolveKernel:
    @pytest.mark.parametrize("m", [256, 1024])
    def test_matches_ref(self, m):
        from repro.kernels.selection_solve.kernel import selection_solve_tiled
        from repro.kernels.selection_solve.ref import selection_solve_ref
        rng = np.random.default_rng(m)
        pg = jnp.asarray(rng.uniform(1e4, 1e8, (m, 128)), jnp.float32)
        bw = jnp.asarray(rng.uniform(5e4, 5e6, (m, 128)), jnp.float32)
        emax = jnp.asarray(np.exp(rng.uniform(-7, 4, (m, 128))), jnp.float32)
        ec = jnp.asarray(np.exp(rng.uniform(-8, -2, (m, 128))), jnp.float32)
        kw = dict(s_bits=6.4e6, tau=0.08, p_max=1.0)
        a_k, p_k = selection_solve_tiled(pg, bw, emax, ec, interpret=True, **kw)
        a_r, p_r = selection_solve_ref(pg, bw, emax, ec, **kw)
        np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_r),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(p_k), np.asarray(p_r),
                                   rtol=1e-5, atol=1e-8)

    def test_ops_wrapper_matches_core_solver(self):
        from repro.kernels.selection_solve.ops import solve_joint_kernel
        prob = sample_problem(5, 100)
        k = solve_joint_kernel(prob, interpret=True)
        o = solve_joint_optimal(prob)
        np.testing.assert_allclose(np.asarray(k.a), np.asarray(o.a),
                                   rtol=1e-4, atol=1e-6)
        assert bool(prob.constraints_satisfied(k.a, k.power).all())


class TestFusedSolveKernel:
    @pytest.mark.parametrize("m", [256, 1024])
    def test_matches_ref(self, m):
        from repro.kernels.selection_solve.kernel import fused_solve_tiled
        from repro.kernels.selection_solve.ref import fused_solve_ref
        rng = np.random.default_rng(m + 1)
        pg = jnp.asarray(rng.uniform(1e4, 1e8, (m, 128)), jnp.float32)
        bw = jnp.asarray(rng.uniform(5e4, 5e6, (m, 128)), jnp.float32)
        emax = jnp.asarray(np.exp(rng.uniform(-7, 4, (m, 128))), jnp.float32)
        ec = jnp.asarray(np.exp(rng.uniform(-8, -2, (m, 128))), jnp.float32)
        kw = dict(s_bits=6.4e6, tau=0.08, p_max=1.0)
        a_k, p_k = fused_solve_tiled(pg, bw, emax, ec, interpret=True, **kw)
        a_r, p_r = fused_solve_ref(pg, bw, emax, ec, **kw)
        np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_r),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(p_k), np.asarray(p_r),
                                   rtol=1e-5, atol=1e-8)

    def test_stationary_past_fixed_point(self):
        """Each element freezes at its first sub-EPS step, so past that
        step a longer trip count changes no bit.  (Without the freeze,
        rounding keeps time-bound elements of this fleet moving for 10+
        steps, and the trip count decides where they stop.)"""
        from repro.kernels.selection_solve.kernel import fused_solve_tiled
        rng = np.random.default_rng(1025)
        shape = (1024, 128)
        pg = jnp.asarray(rng.uniform(1e4, 1e8, shape), jnp.float32)
        bw = jnp.asarray(rng.uniform(5e4, 5e6, shape), jnp.float32)
        emax = jnp.asarray(np.exp(rng.uniform(-7, 4, shape)), jnp.float32)
        ec = jnp.asarray(np.exp(rng.uniform(-8, -2, shape)), jnp.float32)
        kw = dict(s_bits=6.4e6, tau=0.08, p_max=1.0, interpret=True)
        short = fused_solve_tiled(pg, bw, emax, ec, n_iters=10, **kw)
        long = fused_solve_tiled(pg, bw, emax, ec, n_iters=50, **kw)
        for x, y in zip(short, long):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_ops_wrapper_matches_solve_joint(self):
        from repro.core import solve_joint
        from repro.kernels.selection_solve.ops import solve_joint_fused_kernel
        prob = sample_problem(6, 100)
        k = solve_joint_fused_kernel(prob, interpret=True)
        ref = solve_joint(prob)
        np.testing.assert_allclose(np.asarray(k.a), np.asarray(ref.a),
                                   atol=1e-5, rtol=0)
        np.testing.assert_allclose(np.asarray(k.power),
                                   np.asarray(ref.power),
                                   atol=1e-5, rtol=1e-5)
        assert bool(prob.constraints_satisfied(k.a, k.power,
                                               rtol=1e-3).all())

    def test_ops_wrapper_fading(self):
        from repro.core import solve_joint
        from repro.kernels.selection_solve.ops import solve_joint_fused_kernel
        prob = sample_problem(2, 40, with_fading=True, n_rounds=5)
        k = solve_joint_fused_kernel(prob, interpret=True)
        ref = solve_joint(prob)
        assert k.a.shape == (40, 5)
        np.testing.assert_allclose(np.asarray(k.a), np.asarray(ref.a),
                                   atol=1e-5, rtol=0)


# -------------------------------------------------------------- aggregate

class TestMaskedAggregateKernel:
    @pytest.mark.parametrize("n,d", [(64, 512), (128, 2048), (192, 1024)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, n, d, dtype):
        from repro.kernels.masked_aggregate.kernel import masked_aggregate_tiled
        from repro.kernels.masked_aggregate.ref import masked_aggregate_ref
        rng = np.random.default_rng(n + d)
        g = jnp.asarray(rng.normal(size=(n, d)), dtype)
        coef = jnp.asarray(rng.uniform(0, 1, n) * (rng.random(n) > 0.5),
                           jnp.float32)
        out_k = masked_aggregate_tiled(g, coef, interpret=True)
        out_r = masked_aggregate_ref(g, coef)
        tol = 1e-4 if dtype == jnp.float32 else 2e-2   # summation order
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=tol, atol=tol)

    def test_pytree_wrapper_unpadded_shapes(self):
        from repro.kernels.masked_aggregate.ops import masked_aggregate_pytree
        from repro.kernels.masked_aggregate.ref import masked_aggregate_ref
        rng = np.random.default_rng(0)
        tree = {"w": jnp.asarray(rng.normal(size=(10, 33, 7)), jnp.float32),
                "b": jnp.asarray(rng.normal(size=(10, 5)), jnp.float32)}
        coef = jnp.asarray(rng.uniform(0, 1, 10), jnp.float32)
        out = masked_aggregate_pytree(tree, coef, interpret=True)
        for kname, g in tree.items():
            ref = masked_aggregate_ref(g.reshape(10, -1), coef).reshape(g.shape[1:])
            np.testing.assert_allclose(np.asarray(out[kname]), ref,
                                       rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------ swa decode

class TestSWADecodeKernel:
    @pytest.mark.parametrize("w,hkv,g,dh,window", [
        (512, 4, 4, 64, None),
        (1024, 2, 8, 128, 300),
        (512, 1, 4, 128, 128),
        (256, 8, 1, 64, None),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, w, hkv, g, dh, window, dtype):
        from repro.kernels.swa_decode.kernel import swa_decode_tiled
        from repro.kernels.swa_decode.ref import swa_decode_ref
        rng = np.random.default_rng(w + hkv)
        b = 2
        q = jnp.asarray(rng.normal(size=(b, hkv, g, dh)), dtype) * dh ** -0.5
        k = jnp.asarray(rng.normal(size=(b, w, hkv, dh)), dtype)
        v = jnp.asarray(rng.normal(size=(b, w, hkv, dh)), dtype)
        qpos = jnp.int32(w + 5)
        pos = jnp.where(jnp.arange(w) < w - 3, jnp.arange(w), -1).astype(jnp.int32)
        blk = 128 if w % 128 == 0 else w
        out_k = swa_decode_tiled(q, k, v, pos, qpos, window=window,
                                 kv_blk=min(blk, w), interpret=True)
        out_r = swa_decode_ref(q, k, v, pos, qpos, window=window)
        tol = 2e-5 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(
            np.asarray(out_k, np.float32), np.asarray(out_r, np.float32),
            rtol=tol, atol=tol)

    def test_ops_matches_layer_attention(self):
        """decode_attention == layers._attend_block on a ring cache."""
        from repro.kernels.swa_decode.ops import decode_attention
        from repro.models import layers as L
        rng = np.random.default_rng(3)
        b, h, hkv, dh, w = 2, 8, 2, 64, 256
        spec = L.AttnLayerSpec(n_heads=h, n_kv_heads=hkv, d_head=dh,
                               theta=1e4, window=100, softcap=None,
                               qk_norm=False, use_rope=False)
        q = jnp.asarray(rng.normal(size=(b, 1, h, dh)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, w, hkv, dh)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, w, hkv, dh)), jnp.float32)
        pos_buf = jnp.arange(w, dtype=jnp.int32)
        qpos = jnp.int32(w - 1)
        ref = L._attend_block(q, L._repeat_kv(k, h), L._repeat_kv(v, h),
                              qpos[None], pos_buf, spec)
        out = decode_attention(q, k, v, pos_buf, qpos, window=100,
                               n_heads=h, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)


# --------------------------------------------------------------- ssd scan

class TestSSDScanKernel:
    @pytest.mark.parametrize("s,p,n,chunk", [
        (256, 64, 32, 64),
        (512, 32, 64, 128),
        (128, 64, 16, 128),   # single chunk
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_sequential_ref(self, s, p, n, chunk, dtype):
        from repro.kernels.ssd_scan.kernel import ssd_scan_tiled
        from repro.kernels.ssd_scan.ref import ssd_scan_ref
        rng = np.random.default_rng(s + p)
        bh = 3
        x = jnp.asarray(rng.normal(size=(bh, s, p)), dtype)
        dt = jnp.asarray(rng.uniform(0.001, 0.1, (bh, s)), jnp.float32)
        a = jnp.asarray(-rng.uniform(0.5, 4.0, bh), jnp.float32)
        b_mat = jnp.asarray(rng.normal(size=(bh, s, n)) * 0.3, dtype)
        c_mat = jnp.asarray(rng.normal(size=(bh, s, n)) * 0.3, dtype)
        d_skip = jnp.asarray(rng.normal(size=bh), jnp.float32)
        y_k = ssd_scan_tiled(x, dt, a, b_mat, c_mat, d_skip, chunk=chunk,
                             interpret=True)
        y_r = ssd_scan_ref(x, dt, a, b_mat, c_mat, d_skip)
        tol = 1e-4 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(np.asarray(y_k, np.float32),
                                   np.asarray(y_r, np.float32),
                                   rtol=tol, atol=tol)

    def test_ops_matches_model_ssd(self):
        """Kernel wrapper == models.mamba2.ssd_chunked on mamba-shaped ops."""
        from repro.kernels.ssd_scan.ops import ssd_apply
        from repro.models.mamba2 import ssd_chunked
        rng = np.random.default_rng(1)
        b, s, h, p, n = 2, 256, 4, 32, 16
        x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
        dt = jnp.asarray(rng.uniform(0.001, 0.1, (b, s, h)), jnp.float32)
        a = jnp.asarray(-rng.uniform(0.5, 4, h), jnp.float32)
        b_mat = jnp.asarray(rng.normal(size=(b, s, n)) * 0.3, jnp.float32)
        c_mat = jnp.asarray(rng.normal(size=(b, s, n)) * 0.3, jnp.float32)
        d_skip = jnp.asarray(rng.normal(size=h), jnp.float32)
        y_model, _ = ssd_chunked(x, dt, a, b_mat, c_mat, d_skip, chunk=64)
        y_kernel = ssd_apply(x, dt, a, b_mat, c_mat, d_skip, chunk=64,
                             interpret=True)
        np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_model),
                                   rtol=2e-4, atol=2e-4)


# ------------------------------------------------------- interpret rule

def test_interpret_rule_follows_backend():
    """One rule: ``None`` compiles on a TPU and interprets elsewhere; an
    explicit bool always wins.  The library's kernel entry points all
    default to ``None``."""
    import inspect

    import jax

    from repro.kernels import resolve_interpret
    from repro.kernels.masked_aggregate import ops as agg_ops
    from repro.kernels.selection_solve import ops as sel_ops

    assert resolve_interpret(None) is (jax.default_backend() != "tpu")
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    for fn in (sel_ops.solve_joint_kernel, sel_ops.solve_joint_kernel_batch,
               sel_ops.solve_joint_fused_kernel,
               sel_ops.solve_joint_fused_kernel_batch,
               agg_ops.masked_aggregate, agg_ops.quantized_masked_aggregate):
        default = inspect.signature(fn).parameters["interpret"].default
        assert default is None, fn


def test_fused_kernel_default_interpret_matches_explicit():
    """``interpret=None`` off-TPU runs the interpreter: same answer as an
    explicit ``interpret=True``."""
    from repro.kernels.selection_solve.ops import solve_joint_fused_kernel

    prob = sample_problem(7, 40)
    auto = solve_joint_fused_kernel(prob)
    forced = solve_joint_fused_kernel(prob, interpret=True)
    np.testing.assert_array_equal(np.asarray(auto.a), np.asarray(forced.a))
    np.testing.assert_array_equal(np.asarray(auto.power),
                                  np.asarray(forced.power))
