"""Kernel engine: tiled-exact GEMM, fused Pallas conv, cost-model selection.

Three layers of guarantees:
  * property sweep over (K, Cin, Cout, stride, pad, groups): the K-tiled f32
    GEMM and the Pallas interpret-mode conv are bit-identical to the numpy
    refops oracle (the VP's functional model),
  * ``select_kernel`` never resolves a CONV/FC to the scalar integer path,
    and the chosen plan is visible in the Artifacts manifest,
  * full networks (LeNet-5 and a large-K net that crosses the 2^24 exactness
    bound) match the VP byte-for-byte under EVERY kernel plan, on the
    single-image and the batched executor paths.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import engine, graph, perfmodel, quant, refops
from repro.core.executor import _conv_int8, _dot_i8, _fc_int8
from repro.core.pipeline import CompilerPipeline
from repro.kernels.int8_conv.ops import conv2d_int8, fc_int8
from repro.runtime import create_executor

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


def _words(rng, n, max_acc):
    return np.array([quant.pack_scale(*quant.fixed_point(s, max_acc))
                     for s in rng.uniform(1e-5, 1e-3, n)], dtype=np.uint32)


# ---------------------------------------------------------------------------
# Property sweep: kernels vs the refops oracle
# ---------------------------------------------------------------------------
@needs_hypothesis
class TestKernelParitySweep:
    @settings(max_examples=20, deadline=None)
    @given(st.data())
    def test_conv_kernels_match_refops(self, data):
        groups = data.draw(st.sampled_from([1, 2, 4]), label="groups")
        # cin_g up to 140 with k=3 pushes K = cin_g*k*k past EXACT_K=1024,
        # so the sweep covers both the single-tile and the K-tiled regime
        cin_g = data.draw(st.integers(1, 140), label="cin_g")
        cout = groups * data.draw(st.integers(1, 6), label="cout_g")
        k = data.draw(st.sampled_from([1, 3, 5]), label="k")
        stride = data.draw(st.integers(1, 2), label="stride")
        pad = data.draw(st.integers(0, 2), label="pad")
        relu = data.draw(st.booleans(), label="relu")
        cin = groups * cin_g
        h = data.draw(st.integers(max(k - 2 * pad, 1), 8), label="h")
        w = data.draw(st.integers(max(k - 2 * pad, 1), 8), label="w")
        rng = np.random.default_rng(cin * 31 + cout * 7 + k)
        x = rng.integers(-128, 128, (cin, h, w), dtype=np.int8)
        wq = rng.integers(-128, 128, (cout, cin_g * k * k), dtype=np.int8)
        bias = rng.integers(-1000, 1000, cout, dtype=np.int32)
        words = _words(rng, cout, cin_g * k * k * 128 * 128)
        want = refops.conv_int8(x, wq, bias, words, k, stride, pad, groups, relu)

        args = (jnp.asarray(x), jnp.asarray(wq), jnp.asarray(bias),
                jnp.asarray(words.view(np.int32)), k, stride, pad, groups, relu)
        tiled = _conv_int8(*args, perfmodel.KERNEL_GEMM_TILED)
        np.testing.assert_array_equal(np.asarray(tiled), want)
        pallas = conv2d_int8(*args, interpret=True)
        np.testing.assert_array_equal(np.asarray(pallas), want)

    @settings(max_examples=10, deadline=None)
    @given(cin=st.integers(1, 3000), cout=st.integers(1, 8),
           relu=st.booleans())
    def test_fc_kernels_match_refops(self, cin, cout, relu):
        rng = np.random.default_rng(cin + cout)
        x = rng.integers(-128, 128, (cin,), dtype=np.int8)
        wq = rng.integers(-128, 128, (cout, cin), dtype=np.int8)
        bias = rng.integers(-1000, 1000, cout, dtype=np.int32)
        words = _words(rng, cout, cin * 128 * 128)
        want = refops.fc_int8(x.reshape(-1, 1, 1), wq, bias, words, relu)
        ja = (jnp.asarray(x), jnp.asarray(wq), jnp.asarray(bias),
              jnp.asarray(words.view(np.int32)), relu)
        tiled = _fc_int8(*ja, perfmodel.KERNEL_GEMM_TILED)
        np.testing.assert_array_equal(np.asarray(tiled).reshape(-1),
                                      want.reshape(-1))
        pallas = fc_int8(*ja, interpret=True)
        np.testing.assert_array_equal(np.asarray(pallas).reshape(-1),
                                      want.reshape(-1))

class TestTiledExactness:
    def test_tiled_exact_at_boundary(self):
        """K exactly at / one past EXACT_K both stay bit-exact with worst-case
        operands (every product at max magnitude, the adversarial case for
        the 2^24 f32 window)."""
        for kdim in (perfmodel.EXACT_K, perfmodel.EXACT_K + 1):
            a = jnp.full((4, kdim), -128, jnp.int8)
            b = jnp.full((kdim, 4), -128, jnp.int8)
            got = np.asarray(_dot_i8(a, b, (((1,), (0,)), ((), ())), kdim))
            assert (got == kdim * 128 * 128).all()


# ---------------------------------------------------------------------------
# Cost-model selection
# ---------------------------------------------------------------------------
def _conv_desc(kdim: int) -> engine.Descriptor:
    cin = kdim // 9
    return engine.Descriptor(unit="CONV", src_dims=(1, cin, 8, 8),
                             dst_dims=(1, 16, 8, 8), kernel=(3, 3))


class TestSelectKernel:
    def test_small_k_takes_single_exact_gemm_on_cpu(self):
        ch = perfmodel.select_kernel(_conv_desc(576), backend="cpu")
        assert ch.kernel == perfmodel.KERNEL_GEMM_EXACT
        assert ch.k_tiles == 1

    def test_large_k_takes_tiled_never_scalar(self):
        for kdim in (1152, 2304, 4608):
            ch = perfmodel.select_kernel(_conv_desc(kdim), backend="cpu")
            assert ch.kernel == perfmodel.KERNEL_GEMM_TILED
            assert ch.k_tiles == -(-kdim // perfmodel.EXACT_K)

    def test_tpu_profile_prefers_fused_pallas(self):
        ch = perfmodel.select_kernel(_conv_desc(2304), backend="tpu")
        assert ch.kernel == perfmodel.KERNEL_PALLAS

    def test_unknown_platform_has_no_profile(self, monkeypatch):
        import jax
        monkeypatch.setattr(jax, "default_backend", lambda: "npu")
        with pytest.raises(ValueError, match="no backend profile.*'npu'"):
            perfmodel.default_backend()

    def test_forcing_exact_past_bound_raises(self):
        with pytest.raises(ValueError, match="not bit-exact"):
            perfmodel.select_kernel(_conv_desc(2304), backend="cpu",
                                    override=perfmodel.KERNEL_GEMM_EXACT)

    def test_non_gemm_units_are_vpu(self):
        d = engine.Descriptor(unit="PDP", src_dims=(1, 8, 4, 4),
                              dst_dims=(1, 8, 2, 2))
        assert perfmodel.select_kernel(d).kernel == perfmodel.KERNEL_VPU

    def test_no_descriptor_resolves_to_scalar_int(self):
        """Every CONV/FC of every builder net resolves to a GEMM kernel."""
        for name in ("lenet5", "resnet18"):
            g = graph.BUILDERS[name]()
            from repro.core.loadable import build_loadable, calibrate
            params = g.init_params(0)
            cal = calibrate(g, params, np.zeros((1,) + g.input_shape, np.float32))
            ld = build_loadable(g, params, cal)
            for d in ld.descriptors:
                ch = perfmodel.select_kernel(d)
                if d.unit in ("CONV", "FC"):
                    assert ch.kernel in perfmodel.GEMM_KERNELS


# ---------------------------------------------------------------------------
# Whole-network parity vs the VP functional model, under every plan
# ---------------------------------------------------------------------------
def _largek_net() -> graph.NetGraph:
    """Tiny net whose middle conv has K = 128*3*3 = 1152 > EXACT_K."""
    g = graph.NetGraph("largek", (8, 8, 8))
    g.layer(name="data", type="input", inputs=[])
    x = g.layer(name="stem", type="conv", inputs=["data"], out_channels=128,
                kernel=3, pad=1, relu=True)
    x = g.layer(name="big", type="conv", inputs=[x], out_channels=16,
                kernel=3, pad=1, relu=True)
    x = g.layer(name="gap", type="pool", inputs=[x], pool_mode="gap")
    g.layer(name="fc", type="fc", inputs=[x], out_channels=4)
    return g.infer_shapes()


@pytest.fixture(scope="module")
def lenet_art():
    return CompilerPipeline(graph.lenet5()).run()


@pytest.fixture(scope="module")
def largek_art():
    return CompilerPipeline(_largek_net()).run()


class TestNetworkParity:
    @pytest.mark.parametrize("plan", [None, perfmodel.KERNEL_GEMM_TILED,
                                      perfmodel.KERNEL_PALLAS])
    def test_lenet_matches_vp_under_every_plan(self, lenet_art, plan):
        art = lenet_art
        ex = create_executor("baremetal", art, kernel_plan=plan)
        # the VP ran on the pipeline's deterministic sample input
        sample = CompilerPipeline(graph.lenet5()).sample_input
        got = ex.run(sample)
        np.testing.assert_array_equal(got.output_int8.reshape(-1),
                                      art.vp_output_int8.reshape(-1))
        # batched path, padded bucket with dead lanes
        X = np.stack([sample] * 3)
        gb = ex.run_batch(np.concatenate([X, np.zeros_like(X[:1])]), lanes=3)
        for i in range(3):
            np.testing.assert_array_equal(gb.output_int8[i].reshape(-1),
                                          art.vp_output_int8.reshape(-1))

    @pytest.mark.parametrize("plan", [None, perfmodel.KERNEL_GEMM_TILED,
                                      perfmodel.KERNEL_PALLAS])
    def test_largek_net_matches_vp_under_every_plan(self, largek_art, plan):
        art = largek_art
        assert any(e["k_tiles"] > 1 for e in art.kernel_plan), \
            "net must cross the exactness bound"
        ex = create_executor("baremetal", art, kernel_plan=plan)
        sample = CompilerPipeline(_largek_net()).sample_input
        got = ex.run(sample)
        np.testing.assert_array_equal(got.output_int8.reshape(-1),
                                      art.vp_output_int8.reshape(-1))
        gb = ex.run_batch(np.stack([sample] * 2))
        for i in range(2):
            np.testing.assert_array_equal(gb.output_int8[i].reshape(-1),
                                          art.vp_output_int8.reshape(-1))

    def test_resnet18_large_k_path_matches_vp(self):
        """The real large-K workload: ResNet-18's K>1024 layers run tiled and
        the whole net stays byte-identical to the VP, single + batched."""
        pipe = CompilerPipeline(graph.resnet18())
        art = pipe.run()
        tiled = [e for e in art.kernel_plan if e["k_tiles"] > 1]
        assert tiled, "resnet18 must have layers past the exactness bound"
        assert all(e["kernel"] in (perfmodel.KERNEL_GEMM_TILED,
                                   perfmodel.KERNEL_PALLAS) for e in tiled)
        ex = create_executor("baremetal", art)
        got = ex.run(pipe.sample_input)
        np.testing.assert_array_equal(got.output_int8.reshape(-1),
                                      art.vp_output_int8.reshape(-1))
        gb = ex.run_batch(np.stack([pipe.sample_input] * 2))
        for i in range(2):
            np.testing.assert_array_equal(gb.output_int8[i].reshape(-1),
                                          art.vp_output_int8.reshape(-1))

    def test_linuxstack_parity_and_hoisted_binding(self, largek_art):
        ex = create_executor("linuxstack", largek_art)
        ref = create_executor("ref", largek_art)
        # binding is resolved once at construction, not re-parsed per run
        bound = ex._params
        assert all(bool(p) == (d.unit in ("CONV", "FC"))
                   for (d, _), p in zip(ex._ops, bound))
        x = np.random.default_rng(3).normal(
            0, 1, (8, 8, 8)).astype(np.float32)
        np.testing.assert_array_equal(ex.run(x).output_int8,
                                      ref.run(x).output_int8)
        assert ex._params is bound


# ---------------------------------------------------------------------------
# Plan visibility: capabilities + manifest round-trip
# ---------------------------------------------------------------------------
class TestPlanVisibility:
    def test_capabilities_report_kernels(self, largek_art):
        caps = create_executor("baremetal", largek_art).capabilities()
        assert set(caps.kernels) <= set(perfmodel.GEMM_KERNELS)
        assert caps.kernels                      # never empty for a conv net
        forced = create_executor("baremetal", largek_art,
                                 kernel_plan=perfmodel.KERNEL_PALLAS)
        assert forced.capabilities().kernels == (perfmodel.KERNEL_PALLAS,)

    def test_manifest_carries_kernel_plan(self, lenet_art, tmp_path):
        assert lenet_art.kernel_plan, "cost_model must emit a plan"
        convfc = [e for e in lenet_art.kernel_plan
                  if e["unit"] in ("CONV", "FC")]
        assert convfc and all(e["kernel"] in perfmodel.GEMM_KERNELS
                              for e in convfc)
        from repro.core.pipeline import Artifacts
        lenet_art.save(tmp_path / "bundle")
        loaded = Artifacts.load(tmp_path / "bundle")
        assert loaded.kernel_plan == lenet_art.kernel_plan
