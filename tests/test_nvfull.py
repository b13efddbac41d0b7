"""The bf16 ``nv_full`` execution subsystem, end to end.

Four layers of guarantees:
  * kernel parity sweep: the Pallas-interpret bf16 conv/FC kernel and the
    executors' XLA GEMM path stay within the derived single-layer tolerance
    of the numpy ``refops.conv_bf16`` oracle (hypothesis over conv shapes),
  * whole-network tolerance parity: every backend (baremetal single +
    batched with dead-lane padding, linuxstack, ref) matches the VP oracle
    within ``core/tolerances.py``'s per-layer-derived bounds, on the plain
    and the Pallas-interpret kernel plans,
  * compiler/runtime plumbing: bf16 kernel plans round-trip through the
    bundle manifest, ``Session.from_bundle`` serves nv_full, unsupported
    dtypes fail with a descriptive error instead of an assert,
  * mixed-precision serving: an nv_small and an nv_full net coexist in one
    ``Session``/``ServeClient``, each coalescing its own batches (a launch
    never mixes engine dtypes), and ``/v1/nets`` reports config + dtype.
"""

import numpy as np
import jax.numpy as jnp
import ml_dtypes
import pytest

from repro.core import engine, graph, perfmodel, refops, tolerances
from repro.core.executor import _conv_bf16, _fc_bf16
from repro.core.pipeline import Artifacts, CompilerPipeline
from repro.core.tolerances import (assert_close, gemm_tolerance, max_rel_err,
                                   net_tolerance)
from repro.kernels.bf16_conv.ops import conv2d_bf16, fc_bf16
from repro.runtime import Session, create_executor

try:                                    # property sweep is optional; the
    from hypothesis import given, settings, strategies as st   # rest of the
    _HAVE_HYPOTHESIS = True             # module must run without hypothesis
except ImportError:
    _HAVE_HYPOTHESIS = False

    def given(*a, **k):                 # placate decorators at collect time
        return lambda f: f
    settings = given

    class st:                           # noqa: N801
        data = sampled_from = integers = booleans = staticmethod(
            lambda *a, **k: None)

needs_hypothesis = pytest.mark.skipif(
    not _HAVE_HYPOTHESIS, reason="property tests need the optional "
    "hypothesis dep")

BF16_PLANS = [None, perfmodel.KERNEL_GEMM_BF16, perfmodel.KERNEL_PALLAS_BF16]


def _mini_net() -> graph.NetGraph:
    """Small residual net exercising CONV/PDP(max+gap)/EW/FC on nv_full."""
    g = graph.NetGraph("mini_nvfull", (3, 16, 16))
    g.layer(name="data", type="input", inputs=[])
    x = g.layer(name="stem", type="conv", inputs=["data"], out_channels=8,
                kernel=3, stride=1, pad=1, relu=True)
    c1 = g.layer(name="b_c1", type="conv", inputs=[x], out_channels=8,
                 kernel=3, stride=1, pad=1, relu=True)
    c2 = g.layer(name="b_c2", type="conv", inputs=[c1], out_channels=8,
                 kernel=3, stride=1, pad=1)
    x = g.layer(name="b_add", type="add", inputs=[c2, x], relu=True)
    x = g.layer(name="pool", type="pool", inputs=[x], kernel=2, stride=2,
                pool_mode="max")
    x = g.layer(name="gap", type="pool", inputs=[x], pool_mode="gap")
    g.layer(name="fc", type="fc", inputs=[x], out_channels=4)
    return g.infer_shapes()


@pytest.fixture(scope="module")
def mini_pipe():
    return CompilerPipeline(_mini_net(), cfg=engine.NV_FULL)


@pytest.fixture(scope="module")
def mini_art(mini_pipe):
    return mini_pipe.run()


@pytest.fixture(scope="module")
def lenet_full_art():
    return CompilerPipeline(graph.lenet5(), cfg=engine.NV_FULL).run()


# ---------------------------------------------------------------------------
# Tolerance model itself
# ---------------------------------------------------------------------------
class TestToleranceModel:
    def test_single_layer_budget_grows_with_depth(self):
        assert gemm_tolerance(1).rtol < gemm_tolerance(4096).rtol
        assert gemm_tolerance(1).rtol >= tolerances.BF16_EPS

    def test_net_budget_sums_layers(self):
        plan = [{"unit": "CONV", "contract_k": 27},
                {"unit": "PDP", "contract_k": 0},
                {"unit": "FC", "contract_k": 400}]
        want = gemm_tolerance(27).rtol + gemm_tolerance(400).rtol
        assert net_tolerance(plan).rtol == pytest.approx(want)

    def test_assert_close_catches_a_wrong_epilogue(self):
        want = np.array([1.0, 2.0, 3.0])
        with pytest.raises(AssertionError):
            assert_close(want * 1.5, want, gemm_tolerance(9))

    def test_atol_anchored_to_expected_magnitude(self):
        # exact zeros (ReLU) must not make the check vacuous or impossible
        tol = gemm_tolerance(27)
        want = np.array([0.0, 100.0])
        assert_close(np.array([tol.rtol * 50, 100.0]), want, tol)
        with pytest.raises(AssertionError):
            assert_close(np.array([tol.rtol * 500, 100.0]), want, tol)


# ---------------------------------------------------------------------------
# Kernel parity sweep vs the numpy refops oracle
# ---------------------------------------------------------------------------
@needs_hypothesis
class TestBf16KernelParitySweep:
    @settings(max_examples=15, deadline=None)
    @given(st.data())
    def test_conv_kernels_match_refops(self, data):
        groups = data.draw(st.sampled_from([1, 2]), label="groups")
        cin_g = data.draw(st.integers(1, 24), label="cin_g")
        cout = groups * data.draw(st.integers(1, 6), label="cout_g")
        k = data.draw(st.sampled_from([1, 3, 5]), label="k")
        stride = data.draw(st.integers(1, 2), label="stride")
        pad = data.draw(st.integers(0, 2), label="pad")
        relu = data.draw(st.booleans(), label="relu")
        cin = groups * cin_g
        h = data.draw(st.integers(max(k - 2 * pad, 1), 8), label="h")
        w = data.draw(st.integers(max(k - 2 * pad, 1), 8), label="w")
        rng = np.random.default_rng(cin * 31 + cout * 7 + k)
        x = rng.normal(0, 1, (cin, h, w)).astype(ml_dtypes.bfloat16)
        wq = rng.normal(0, 0.5, (cout, cin_g * k * k)).astype(ml_dtypes.bfloat16)
        bias = rng.normal(0, 1, cout).astype(np.float32)
        want = refops.conv_bf16(x, wq, bias, k, stride, pad, groups, relu)
        tol = gemm_tolerance(cin_g * k * k)

        args = (jnp.asarray(x), jnp.asarray(wq), jnp.asarray(bias),
                k, stride, pad, groups, relu)
        gemm = _conv_bf16(*args, perfmodel.KERNEL_GEMM_BF16)
        assert_close(np.asarray(gemm, np.float32), want, tol, "gemm_bf16")
        pallas = conv2d_bf16(*args, interpret=True)
        assert_close(np.asarray(pallas, np.float32), want, tol, "pallas_bf16")

    @settings(max_examples=8, deadline=None)
    @given(cin=st.integers(1, 600), cout=st.integers(1, 8),
           relu=st.booleans())
    def test_fc_kernels_match_refops(self, cin, cout, relu):
        rng = np.random.default_rng(cin + cout)
        x = rng.normal(0, 1, (cin,)).astype(ml_dtypes.bfloat16)
        wq = rng.normal(0, 0.5, (cout, cin)).astype(ml_dtypes.bfloat16)
        bias = rng.normal(0, 1, cout).astype(np.float32)
        want = refops.fc_bf16(x.reshape(-1, 1, 1), wq, bias, relu)
        tol = gemm_tolerance(cin)
        ja = (jnp.asarray(x), jnp.asarray(wq), jnp.asarray(bias), relu)
        gemm = _fc_bf16(*ja, perfmodel.KERNEL_GEMM_BF16)
        assert_close(np.asarray(gemm, np.float32).reshape(-1),
                     want.reshape(-1), tol, "gemm_bf16")
        pallas = fc_bf16(*ja, interpret=True)
        assert_close(np.asarray(pallas, np.float32).reshape(-1),
                     want.reshape(-1), tol, "pallas_bf16")


class TestBf16KernelParityFixed:
    """Hypothesis-free parity spot checks (run even without the optional
    dep): one conv shape per interesting regime, plus the bug-class check."""

    @pytest.mark.parametrize("cin,cout,k,stride,pad,groups,relu", [
        (3, 8, 3, 1, 1, 1, True),
        (8, 4, 5, 2, 2, 1, False),
        (8, 8, 3, 1, 0, 2, True),      # grouped
        (1, 2, 1, 1, 0, 1, False),     # 1x1 degenerate
    ])
    def test_conv_parity_fixed(self, cin, cout, k, stride, pad, groups, relu):
        rng = np.random.default_rng(cin * 13 + cout)
        h = w = 8
        cin_g = cin // groups
        x = rng.normal(0, 1, (cin, h, w)).astype(ml_dtypes.bfloat16)
        wq = rng.normal(0, 0.5, (cout, cin_g * k * k)).astype(ml_dtypes.bfloat16)
        bias = rng.normal(0, 1, cout).astype(np.float32)
        want = refops.conv_bf16(x, wq, bias, k, stride, pad, groups, relu)
        tol = gemm_tolerance(cin_g * k * k)
        args = (jnp.asarray(x), jnp.asarray(wq), jnp.asarray(bias),
                k, stride, pad, groups, relu)
        gemm = _conv_bf16(*args, perfmodel.KERNEL_GEMM_BF16)
        assert_close(np.asarray(gemm, np.float32), want, tol, "gemm_bf16")
        pallas = conv2d_bf16(*args, interpret=True)
        assert_close(np.asarray(pallas, np.float32), want, tol, "pallas_bf16")

    def test_bf16_accumulator_would_fail_the_budget(self):
        """The tolerance is tight enough to catch a bf16 (not f32)
        accumulator on a deep contraction — the bug class it exists for."""
        rng = np.random.default_rng(0)
        kdim = 4096
        x = rng.normal(0, 1, (kdim,)).astype(ml_dtypes.bfloat16)
        w = rng.normal(0, 1, (4, kdim)).astype(ml_dtypes.bfloat16)
        bias = np.zeros(4, np.float32)
        want = refops.fc_bf16(x.reshape(-1, 1, 1), w, bias)
        # sequential bf16 accumulation (the bug)
        acc = np.zeros(4, ml_dtypes.bfloat16)
        for i in range(kdim):
            acc = (acc + w[:, i] * x[i]).astype(ml_dtypes.bfloat16)
        with pytest.raises(AssertionError):
            assert_close(acc.astype(np.float32), want.reshape(-1),
                         gemm_tolerance(kdim))


# ---------------------------------------------------------------------------
# Kernel selection for the bf16 family
# ---------------------------------------------------------------------------
def _conv_desc(kdim: int) -> engine.Descriptor:
    cin = kdim // 9
    return engine.Descriptor(unit="CONV", src_dims=(1, cin, 8, 8),
                             dst_dims=(1, 16, 8, 8), kernel=(3, 3))


class TestBf16KernelSelection:
    def test_cpu_resolves_gemm_bf16(self):
        ch = perfmodel.select_kernel(_conv_desc(1152), backend="cpu",
                                     dtype="bf16")
        assert ch.kernel == perfmodel.KERNEL_GEMM_BF16
        assert ch.k_tiles == 1          # f32 accumulate never needs K tiling

    def test_tpu_prefers_fused_pallas_bf16(self):
        ch = perfmodel.select_kernel(_conv_desc(1152), backend="tpu",
                                     dtype="bf16")
        assert ch.kernel == perfmodel.KERNEL_PALLAS_BF16

    @pytest.mark.parametrize("batch", [1, 2, 8])
    def test_tpu_prefers_fused_pallas_bf16_for_fc(self, batch):
        # a GEMV is bound by the weight stream, which both kernels pay
        # alike; the unfused GEMM also moves its f32 accumulator through HBM
        fc = engine.Descriptor(unit="FC", src_dims=(1, 2048, 1, 1),
                               dst_dims=(1, 1000, 1, 1), kernel=(1, 1))
        ch = perfmodel.select_kernel(fc, backend="tpu", dtype="bf16",
                                     batch=batch)
        assert ch.kernel == perfmodel.KERNEL_PALLAS_BF16

    def test_int8_kernel_forced_on_bf16_raises(self):
        with pytest.raises(ValueError, match="bf16"):
            perfmodel.select_kernel(_conv_desc(576), backend="cpu",
                                    dtype="bf16",
                                    override=perfmodel.KERNEL_GEMM_TILED)

    def test_bf16_kernel_forced_on_int8_raises(self):
        with pytest.raises(ValueError, match="int8"):
            perfmodel.select_kernel(_conv_desc(576), backend="cpu",
                                    override=perfmodel.KERNEL_GEMM_BF16)

    def test_unknown_dtype_raises(self):
        with pytest.raises(ValueError, match="kernel family"):
            perfmodel.select_kernel(_conv_desc(576), dtype="fp4")

    def test_executor_rejects_cross_family_plan(self, mini_art):
        with pytest.raises(ValueError, match="bf16"):
            create_executor("baremetal", mini_art,
                            kernel_plan=perfmodel.KERNEL_PALLAS)


# ---------------------------------------------------------------------------
# Whole-network tolerance parity vs the VP functional model
# ---------------------------------------------------------------------------
class TestNetworkParity:
    @pytest.mark.parametrize("plan", BF16_PLANS)
    def test_mini_net_matches_vp_under_every_plan(self, mini_pipe, mini_art,
                                                  plan):
        art = mini_art
        tol = net_tolerance(art.kernel_plan)
        ex = create_executor("baremetal", art, kernel_plan=plan)
        sample = mini_pipe.sample_input
        got = ex.run(sample)
        assert_close(got.output, art.vp_output, tol, f"single plan={plan}")
        # raw engine bytes carry the bf16 stream, like VpResult
        assert got.output_int8.dtype == np.uint8
        # batched path: padded bucket with a dead lane
        X = np.stack([sample] * 3)
        gb = ex.run_batch(np.concatenate([X, np.zeros_like(X[:1])]), lanes=3)
        assert gb.output.shape[0] == 3
        for i in range(3):
            assert_close(gb.output[i], art.vp_output, tol,
                         f"batched lane {i} plan={plan}")

    def test_lenet_full_matches_vp(self, lenet_full_art):
        art = lenet_full_art
        pipe = CompilerPipeline(graph.lenet5(), cfg=engine.NV_FULL)
        tol = net_tolerance(art.kernel_plan)
        got = create_executor("baremetal", art).run(pipe.sample_input)
        assert_close(got.output, art.vp_output, tol, "lenet5 nv_full")
        assert max_rel_err(got.output, art.vp_output) <= tol.rtol

    def test_linuxstack_and_ref_parity(self, mini_pipe, mini_art):
        tol = net_tolerance(mini_art.kernel_plan)
        x = mini_pipe.sample_input
        for kind in ("linuxstack", "ref"):
            got = create_executor(kind, mini_art).run(x)
            assert_close(got.output, mini_art.vp_output, tol, kind)

    def test_capabilities_report_bf16(self, mini_art):
        caps = create_executor("baremetal", mini_art).capabilities()
        assert caps.dtype == "bf16"
        assert set(caps.kernels) <= set(perfmodel.BF16_KERNELS)
        assert caps.kernels


# ---------------------------------------------------------------------------
# Compiler / runtime plumbing
# ---------------------------------------------------------------------------
class TestBf16Plumbing:
    def test_kernel_plan_round_trips_through_bundle(self, mini_art, tmp_path):
        convfc = [e for e in mini_art.kernel_plan
                  if e["unit"] in ("CONV", "FC")]
        assert convfc and all(e["kernel"] in perfmodel.BF16_KERNELS
                              for e in convfc)
        assert all(e["dtype"] == "bf16" for e in mini_art.kernel_plan)
        mini_art.save(tmp_path / "bundle")
        loaded = Artifacts.load(tmp_path / "bundle")
        assert loaded.kernel_plan == mini_art.kernel_plan
        assert loaded.cfg == engine.NV_FULL        # manifest carries the config

    def test_session_serves_a_loaded_nvfull_bundle(self, mini_pipe, mini_art,
                                                   tmp_path):
        mini_art.save(tmp_path / "bundle")
        tol = net_tolerance(mini_art.kernel_plan)
        with Session.from_bundle(tmp_path / "bundle") as ses:
            got = ses.run(mini_pipe.sample_input)
            assert_close(got.output, mini_art.vp_output, tol, "from_bundle")

    def test_unknown_dtype_fails_with_actionable_error(self, mini_art):
        from repro.core.executor import BareMetalExecutor
        bad = engine.EngineConfig(name="nv_fp4", dtype="fp4", macs=64,
                                  dbb_bytes_per_cycle=8, conv_buf_kib=128)
        with pytest.raises(NotImplementedError) as ei:
            BareMetalExecutor(mini_art.trace, mini_art.weight_image, bad)
        msg = str(ei.value)
        assert "nv_small" in msg and "nv_full" in msg and "fp4" in msg

    def test_unknown_dtype_loadable_fails_with_actionable_error(self):
        from repro.core.loadable import build_loadable, calibrate
        g = _mini_net()
        params = g.init_params(0)
        cal = calibrate(g, params, np.zeros((1,) + g.input_shape, np.float32))
        bad = engine.EngineConfig(name="nv_fp4", dtype="fp4", macs=64,
                                  dbb_bytes_per_cycle=8, conv_buf_kib=128)
        with pytest.raises(ValueError, match="fp4"):
            build_loadable(g, params, cal, bad)


# ---------------------------------------------------------------------------
# Mixed-precision serving: nv_small and nv_full side by side
# ---------------------------------------------------------------------------
class TestMixedPrecisionServing:
    @pytest.fixture(scope="class")
    def both_arts(self):
        g = _mini_net()
        small = CompilerPipeline(g).run()
        pipe_full = CompilerPipeline(g, cfg=engine.NV_FULL)
        full = pipe_full.run()
        return small, full, pipe_full.sample_input

    def test_two_configs_coexist_without_cross_dtype_mixing(self, both_arts):
        small, full, x = both_arts
        tol = net_tolerance(full.kernel_plan)
        with Session(small, name="small") as ses:
            ses.load(full, name="full")
            want_small = ses.run(x, net="small")
            # interleave concurrent submits against both nets; each net's
            # dispatcher coalesces its own batches (one launch never mixes
            # engine dtypes — a dispatcher serves exactly one net/config)
            futs = []
            for _ in range(8):
                futs.append(("full", ses.submit(x, net="full")))
                futs.append(("small", ses.submit(x, net="small")))
            for net, f in futs:
                res = f.result(timeout=60)
                if net == "full":
                    assert_close(res.output, full.vp_output, tol, "served")
                    assert res.output_int8.dtype == np.uint8
                else:
                    np.testing.assert_array_equal(res.output_int8,
                                                  want_small.output_int8)
            # both nets actually coalesced (their own buckets, not 1-by-1)
            assert ses.stats("full").coalesce_max >= 2
            assert ses.stats("small").coalesce_max >= 2

    def test_bf16_net_canonicalises_int8_inputs_to_float(self, both_arts):
        _, full, x = both_arts
        tol = net_tolerance(full.kernel_plan)
        with Session(full, name="full") as ses:
            xi8 = np.clip(x, -1, 1)
            want = ses.run(xi8.astype(np.float32), net="full")
            # an int8 array is float-converted for a bf16 net, never treated
            # as pre-quantised engine bytes
            got = ses.run(xi8.astype(np.float32).astype(np.int8), net="full")
            assert_close(got.output,
                         ses.run(xi8.astype(np.int8).astype(np.float32),
                                 net="full").output, tol)
            assert want.output.shape == got.output.shape

    def test_serve_client_reports_config_and_dtype(self, both_arts):
        small, full, x = both_arts
        from repro.serve.client import ServeClient
        with Session(small, name="small") as ses:
            ses.load(full, name="full")
            client = ServeClient(ses)
            nets = {n["name"]: n for n in client.nets()}
            assert nets["small"]["config"] == "nv_small"
            assert nets["small"]["dtype"] == "int8"
            assert nets["full"]["config"] == "nv_full"
            assert nets["full"]["dtype"] == "bf16"
            assert nets["full"]["input_shape"] == [3, 16, 16]
            # inference through the serving front door, both precisions
            tol = net_tolerance(full.kernel_plan)
            assert_close(client.infer("full", x).output, full.vp_output, tol)
            np.testing.assert_array_equal(
                client.infer("small", x).output_int8,
                ses.run(x, net="small").output_int8)
