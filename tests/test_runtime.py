"""Pipeline + Session API tests: staging, caching, bundles, batching, registry."""

import warnings

import numpy as np
import pytest

from repro.core import api, graph, pipeline
from repro.runtime import Session, backend_names, create_executor, \
    register_backend


def _residual_net() -> graph.NetGraph:
    """Small residual net: exercises the EW aux path of the batch dataflow plan."""
    g = graph.NetGraph("resid", (3, 12, 12))
    g.layer(name="data", type="input", inputs=[])
    x = g.layer(name="stem", type="conv", inputs=["data"], out_channels=6,
                kernel=3, pad=1, relu=True)
    c1 = g.layer(name="c1", type="conv", inputs=[x], out_channels=6,
                 kernel=3, pad=1, relu=True)
    c2 = g.layer(name="c2", type="conv", inputs=[c1], out_channels=6,
                 kernel=3, pad=1)
    x = g.layer(name="add", type="add", inputs=[c2, x], relu=True)
    x = g.layer(name="gap", type="pool", inputs=[x], pool_mode="gap")
    g.layer(name="fc", type="fc", inputs=[x], out_channels=4)
    return g.infer_shapes()


def _stride_pad_net() -> graph.NetGraph:
    """Stride/pad-heavy graph: odd strides + asymmetric-ish padding paths."""
    g = graph.NetGraph("stride_pad", (3, 17, 17))
    g.layer(name="data", type="input", inputs=[])
    x = g.layer(name="c1", type="conv", inputs=["data"], out_channels=8,
                kernel=5, stride=2, pad=2, relu=True)
    x = g.layer(name="c2", type="conv", inputs=[x], out_channels=12,
                kernel=3, stride=2, pad=1, relu=True)
    x = g.layer(name="p1", type="pool", inputs=[x], kernel=3, stride=2, pad=1,
                pool_mode="max")
    x = g.layer(name="c3", type="conv", inputs=[x], out_channels=16,
                kernel=3, stride=1, pad=0, relu=True)
    g.layer(name="fc", type="fc", inputs=[x], out_channels=5)
    return g.infer_shapes()


@pytest.fixture(scope="module")
def lenet_art():
    return pipeline.CompilerPipeline(graph.lenet5()).run()


@pytest.fixture(scope="module")
def stride_art():
    return pipeline.CompilerPipeline(_stride_pad_net()).run()


@pytest.fixture(scope="module")
def resid_art():
    return pipeline.CompilerPipeline(_residual_net()).run()


# ---------------------------------------------------------------------------
# CompilerPipeline: staged execution + content-hash caching
# ---------------------------------------------------------------------------
class TestPipeline:
    def test_stages_run_individually(self):
        pipe = pipeline.CompilerPipeline(graph.lenet5())
        cal = pipe.run_stage("calibrate")
        assert set(pipe.results) == {"calibrate"}
        assert "data" in cal.scales
        trace = pipe.run_stage("parse_trace")
        assert trace.n_writes > 0
        # parse_trace pulled in its deps but not the independent stages
        assert "assemble" not in pipe.results
        assert "cost_model" not in pipe.results

    def test_cost_model_skips_vp(self):
        """cost_model depends only on the loadable — no VP execution."""
        pipe = pipeline.CompilerPipeline(_stride_pad_net(), use_cache=False)
        cost = pipe.run_stage("cost_model")
        assert cost.total_cycles > 0
        assert "vp_run" not in pipe.results

    def test_unknown_stage_raises(self):
        pipe = pipeline.CompilerPipeline(graph.lenet5())
        with pytest.raises(ValueError, match="unknown stage"):
            pipe.run_stage("link")

    def test_content_hash_cache(self):
        g = _stride_pad_net()
        pipeline.clear_cache()
        art1 = pipeline.CompilerPipeline(g).run()
        misses = pipeline.cache_stats()["misses"]
        art2 = pipeline.CompilerPipeline(_stride_pad_net()).run()
        stats = pipeline.cache_stats()
        assert stats["misses"] == misses          # second compile: all hits
        assert stats["hits"] >= len(pipeline.STAGE_NAMES)
        assert art2.trace_text == art1.trace_text
        # different params -> different content hash -> recompile (the register
        # trace is param-independent; the extracted weight image is not)
        art3 = pipeline.CompilerPipeline(g, params=g.init_params(1)).run()
        assert pipeline.cache_stats()["misses"] > misses
        assert art3.weight_image != art1.weight_image

    def test_matches_legacy_compile_network(self, lenet_art):
        with pytest.warns(DeprecationWarning):
            legacy = api.compile_network(graph.lenet5())
        assert legacy.trace_text == lenet_art.trace_text
        assert legacy.program_binary == lenet_art.program_binary

    def test_disk_cache_hits_across_processes(self, tmp_path, monkeypatch):
        """Second pipeline (fresh 'process') must load stages from disk —
        including vp_run — instead of re-executing the VP."""
        cache = tmp_path / "stagecache"
        g = _stride_pad_net()
        art1 = pipeline.CompilerPipeline(g, cache_dir=cache).run()
        assert list(cache.glob("*.pkl"))
        pipeline.clear_cache()                  # simulate a new process
        import repro.core.vp
        monkeypatch.setattr(repro.core.vp.VirtualPlatform, "run",
                            lambda *a, **k: pytest.fail("VP re-executed"))
        art2 = pipeline.CompilerPipeline(_stride_pad_net(),
                                         cache_dir=cache).run()
        assert art2.trace_text == art1.trace_text
        assert art2.weight_image == art1.weight_image
        assert pipeline.cache_stats()["disk_hits"] >= len(pipeline.STAGE_NAMES)
        assert pipeline.cache_stats()["misses"] == 0

    def test_disk_cache_eviction_cap(self, tmp_path):
        cache = tmp_path / "tiny"
        pipeline.clear_cache()
        pipeline.CompilerPipeline(_stride_pad_net(), cache_dir=cache,
                                  cache_dir_max_bytes=0).run()
        assert list(cache.glob("*.pkl")) == []   # everything evicted
        cache2 = tmp_path / "big"
        pipeline.clear_cache()
        pipeline.CompilerPipeline(_stride_pad_net(), cache_dir=cache2).run()
        assert len(list(cache2.glob("*.pkl"))) == len(pipeline.STAGE_NAMES)

    def test_disk_cache_corrupt_entry_is_miss(self, tmp_path):
        cache = tmp_path / "c"
        pipeline.CompilerPipeline(_stride_pad_net(), cache_dir=cache).run()
        for f in cache.glob("*.pkl"):
            f.write_bytes(b"\x80garbage")
        pipeline.clear_cache()
        art = pipeline.CompilerPipeline(_stride_pad_net(),
                                        cache_dir=cache).run()
        assert art.trace.n_writes > 0            # recomputed fine
        assert pipeline.cache_stats()["disk_hits"] == 0


# ---------------------------------------------------------------------------
# Artifacts bundle: save/load round-trip, no recompilation
# ---------------------------------------------------------------------------
class TestBundle:
    def test_roundtrip_bit_exact_without_vp(self, lenet_art, tmp_path,
                                            monkeypatch):
        bundle = lenet_art.save(tmp_path / "lenet")
        assert sorted(f.name for f in bundle.iterdir()) == \
            ["manifest.json", "program.bin", "trace.cfg", "weights.img"]

        # loading + serving the bundle must never touch the VP or compiler
        import repro.core.vp
        monkeypatch.setattr(repro.core.vp.VirtualPlatform, "run",
                            lambda *a, **k: pytest.fail("VP re-executed"))
        ses = Session.from_bundle(bundle)
        x = np.random.default_rng(3).normal(0, 1, (1, 28, 28)).astype(np.float32)
        fresh = Session(lenet_art).run(x)
        np.testing.assert_array_equal(ses.run(x).output_int8, fresh.output_int8)

    def test_loaded_artifacts_report_same_storage(self, lenet_art, tmp_path):
        loaded = pipeline.Artifacts.load(lenet_art.save(tmp_path / "b"))
        assert loaded.storage_report() == lenet_art.storage_report()
        assert loaded.loadable is None and loaded.cost is None

    def test_load_rejects_non_bundle(self, tmp_path):
        with pytest.raises(FileNotFoundError, match="not an artifact bundle"):
            pipeline.Artifacts.load(tmp_path)

    def test_load_truncated_weight_image(self, lenet_art, tmp_path):
        b = lenet_art.save(tmp_path / "b")
        img = b / "weights.img"
        img.write_bytes(img.read_bytes()[:-16])
        with pytest.raises(ValueError, match="truncated weight image"):
            pipeline.Artifacts.load(b)

    def test_load_manifest_version_mismatch(self, lenet_art, tmp_path):
        import json
        b = lenet_art.save(tmp_path / "b")
        m = json.loads((b / "manifest.json").read_text())
        m["format"] = 99
        (b / "manifest.json").write_text(json.dumps(m))
        with pytest.raises(ValueError, match="unsupported bundle format"):
            pipeline.Artifacts.load(b)

    def test_load_corrupt_manifest(self, lenet_art, tmp_path):
        b = lenet_art.save(tmp_path / "b")
        (b / "manifest.json").write_text("{not json at all")
        with pytest.raises(ValueError, match="corrupt manifest"):
            pipeline.Artifacts.load(b)

    def test_load_missing_weight_image(self, lenet_art, tmp_path):
        b = lenet_art.save(tmp_path / "b")
        (b / "weights.img").unlink()
        with pytest.raises(FileNotFoundError, match="weights.img"):
            pipeline.Artifacts.load(b)


# ---------------------------------------------------------------------------
# Session: batching, multi-network residency, stats
# ---------------------------------------------------------------------------
class TestSession:
    @pytest.mark.parametrize("backend", ["baremetal", "linuxstack"])
    @pytest.mark.parametrize("which", ["lenet", "stride", "resid"])
    def test_run_batch_bitexact_vs_sequential(self, backend, which, lenet_art,
                                              stride_art, resid_art, request):
        art = {"lenet": lenet_art, "stride": stride_art,
               "resid": resid_art}[which]
        shape = {"lenet": (1, 28, 28), "stride": (3, 17, 17),
                 "resid": (3, 12, 12)}[which]
        ses = Session(art, backend=backend)
        X = np.random.default_rng(5).normal(0, 1, (8,) + shape).astype(np.float32)
        batched = ses.run_batch(X)
        seq_i8 = np.stack([ses.run(x).output_int8 for x in X])
        assert batched.output_int8.shape == (8, art.output_elems)
        np.testing.assert_array_equal(batched.output_int8, seq_i8)

    def test_dot_i8_exactness_bound(self):
        """Adversarial int8 data at the f32-exactness boundary (K around 1024).

        K=1024 is the largest contraction where the worst-case accumulator
        K*16384 = 2^24 is still an exact f32 integer; K=1025 must take the
        int32 path (all-(-128) operands would round in f32).
        """
        import jax.numpy as jnp
        from repro.core.executor import _dot_i8
        dn = (((1,), (0,)), ((), ()))
        for k_dim in (1024, 1025, 1031):
            a = jnp.full((2, k_dim), -128, jnp.int8)
            b = jnp.full((k_dim,), -128, jnp.int8)
            b = b.at[0].set(-127)           # true sum = K*16384 - 128
            got = np.asarray(_dot_i8(a, b, dn, k_dim))
            want = (np.full((2, k_dim), -128, np.int64)
                    @ np.asarray(b, np.int64)).astype(np.int32)
            np.testing.assert_array_equal(got, want)

    def test_large_contraction_int32_path(self):
        """K*128*128 > 2^24 disables the exact-f32 GEMM; must stay VP-exact."""
        from repro.core.vp import VirtualPlatform
        g = graph.NetGraph("bigk", (520, 4, 4))     # K = 520*9 = 4680
        g.layer(name="data", type="input", inputs=[])
        x = g.layer(name="c1", type="conv", inputs=["data"], out_channels=8,
                    kernel=3, pad=1, relu=True)
        g.layer(name="fc", type="fc", inputs=[x], out_channels=3)
        art = pipeline.CompilerPipeline(g.infer_shapes()).run()
        xi = np.random.default_rng(0).normal(0, 1, g.input_shape).astype(np.float32)
        vp = VirtualPlatform(art.loadable).run(xi)
        ex = create_executor("baremetal", art)
        np.testing.assert_array_equal(ex.run(xi).output_int8, vp.output_int8)
        X = np.random.default_rng(1).normal(0, 1, (4,) + g.input_shape).astype(np.float32)
        np.testing.assert_array_equal(
            ex.run_batch(X).output_int8,
            np.stack([ex.run(v).output_int8 for v in X]))

    def test_ref_backend_parity(self, stride_art):
        x = np.random.default_rng(6).normal(0, 1, (3, 17, 17)).astype(np.float32)
        out = {b: create_executor(b, stride_art).run(x).output_int8
               for b in ("baremetal", "linuxstack", "ref")}
        np.testing.assert_array_equal(out["ref"], out["baremetal"])
        np.testing.assert_array_equal(out["ref"], out["linuxstack"])

    def test_multi_network_residency(self, lenet_art, stride_art):
        ses = Session(lenet_art)
        ses.load(stride_art, backend="linuxstack")
        assert ses.networks == ["lenet5", "stride_pad"]
        x = np.random.default_rng(7).normal(0, 1, (3, 17, 17)).astype(np.float32)
        y = ses.run(x, net="stride_pad")
        assert y.output_int8.shape == (stride_art.output_elems,)
        assert ses.stats("stride_pad").calls == 1
        assert ses.stats("lenet5").calls == 0
        with pytest.raises(ValueError, match="already resident"):
            ses.load(lenet_art)
        with pytest.raises(KeyError, match="no resident network"):
            ses.run(x, net="resnet99")

    def test_arena_stays_resident(self, lenet_art):
        ex = create_executor("baremetal", lenet_art)
        x = np.random.default_rng(8).normal(0, 1, (1, 28, 28)).astype(np.float32)
        first = ex.run(x)
        params_after_first = ex._params_dev
        assert params_after_first is not None
        second = ex.run(x)              # replays over the resident weights
        assert ex._params_dev is params_after_first
        np.testing.assert_array_equal(first.output_int8, second.output_int8)
        ex.reset_arena()
        third = ex.run(x)
        np.testing.assert_array_equal(first.output_int8, third.output_int8)


# ---------------------------------------------------------------------------
# Registry + deprecation shims
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_builtins_registered(self):
        assert {"baremetal", "linuxstack", "ref"} <= set(backend_names())

    def test_unknown_backend_raises_with_list(self, lenet_art):
        with pytest.raises(ValueError, match="baremetal, linuxstack, ref"):
            create_executor("gpu", lenet_art)
        with pytest.warns(DeprecationWarning), \
                pytest.raises(ValueError, match="registered backends"):
            api.make_executor(lenet_art, "typo")

    def test_custom_backend_decorator(self, lenet_art):
        from repro.core.executor import ExecutorCapabilities

        class _Echo:
            def __init__(self, art):
                self.name = art.graph_name

            def run(self, x):
                return ("echo", self.name)

            def run_batch(self, X, lanes=None):
                return ("echo-batch", self.name)

            def capabilities(self):
                return ExecutorCapabilities()

        @register_backend("echo-test")
        def _echo(art, **kw):
            return _Echo(art)
        try:
            ex = create_executor("echo-test", lenet_art)
            assert ex.run(None) == ("echo", "lenet5")
        finally:
            from repro.runtime import registry
            registry._BACKENDS.pop("echo-test", None)

    def test_nonconforming_backend_rejected(self, lenet_art):
        """Factories must return ExecutorBackend-conformant objects; anything
        else is rejected at create() time with the missing methods named."""
        @register_backend("broken-test")
        def _broken(art, **kw):
            return ("not", "an", "executor")
        try:
            with pytest.raises(TypeError, match="ExecutorBackend.*missing"):
                create_executor("broken-test", lenet_art)
        finally:
            from repro.runtime import registry
            registry._BACKENDS.pop("broken-test", None)

    def test_make_executor_shim_warns_and_works(self, lenet_art):
        x = np.random.default_rng(9).normal(0, 1, (1, 28, 28)).astype(np.float32)
        with pytest.warns(DeprecationWarning):
            ex = api.make_executor(lenet_art, "baremetal")
        ref = Session(lenet_art).run(x)
        np.testing.assert_array_equal(ex.run(x).output_int8, ref.output_int8)


# ---------------------------------------------------------------------------
# Persistent compile cache (entry points only)
# ---------------------------------------------------------------------------
class TestCompileCache:
    @pytest.fixture
    def cache_config(self):
        import jax
        old = jax.config.jax_compilation_cache_dir
        yield jax.config
        jax.config.update("jax_compilation_cache_dir", old)

    def test_env_dir_is_kept(self, cache_config, monkeypatch, tmp_path):
        from repro.runtime.compile_cache import enable_compile_cache
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = cache_config.jax_compilation_cache_dir
        assert enable_compile_cache() == str(tmp_path)
        assert cache_config.jax_compilation_cache_dir == before

    def test_default_dir_is_fixed_in_checkout(self, cache_config,
                                              monkeypatch):
        import pathlib
        from repro.runtime.compile_cache import enable_compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        root = pathlib.Path(__file__).resolve().parents[1]
        assert enable_compile_cache() == str(root / ".jax_cache")
        assert cache_config.jax_compilation_cache_dir == str(
            root / ".jax_cache")

    def test_programs_land_in_env_dir(self, tmp_path):
        import os
        import subprocess
        import sys
        code = ("from repro.runtime.compile_cache import enable_compile_cache\n"
                "enable_compile_cache()\n"
                "import jax\n"
                "jax.jit(lambda x: x * 3 + 1)(2.0).block_until_ready()\n")
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                   PYTHONPATH=os.path.join(root, "src"))
        r = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr
        assert any(tmp_path.iterdir())


# ---------------------------------------------------------------------------
# Executor dataflow: arena reads resolve to the values last written there
# ---------------------------------------------------------------------------
class TestReadPieces:
    def test_exact_region_forwards_one_value(self):
        from repro.core.executor import _read_pieces
        writes = [(-1, 0, 64), (0, 64, 128)]
        assert _read_pieces(writes, 64, 128, 1) == [(0, 0, 64)]

    def test_concat_reads_producers_side_by_side(self):
        from repro.core.executor import _read_pieces
        writes = [(-1, 0, 64), (0, 64, 96), (1, 96, 160)]
        assert _read_pieces(writes, 64, 160, 2) == [(0, 0, 16), (1, 0, 32)]

    def test_latest_write_wins(self):
        from repro.core.executor import _read_pieces
        # op 1 reuses the middle of op 0's surface (liveness-planned arena)
        writes = [(-1, 0, 64), (0, 64, 192), (1, 96, 128)]
        assert _read_pieces(writes, 64, 192, 1) == [
            (0, 0, 32), (1, 0, 32), (0, 64, 128)]

    def test_unwritten_bytes_are_an_error(self):
        from repro.core.executor import _read_pieces
        with pytest.raises(ValueError, match="read before"):
            _read_pieces([(-1, 0, 64)], 32, 96, 1)

    def test_concat_net_batch_matches_single(self):
        net = graph.NetGraph("cat", (3, 6, 6))
        net.layer(name="data", type="input", inputs=[])
        b1 = net.layer(name="b1", type="conv", inputs=["data"],
                       out_channels=4, kernel=1, relu=True)
        b2 = net.layer(name="b2", type="conv", inputs=["data"],
                       out_channels=5, kernel=3, pad=1, relu=True)
        x = net.layer(name="cat", type="concat", inputs=[b1, b2])
        x = net.layer(name="gap", type="pool", inputs=[x], pool_mode="gap")
        net.layer(name="fc", type="fc", inputs=[x], out_channels=3)
        art = pipeline.CompilerPipeline(net.infer_shapes()).run()
        ex = create_executor("baremetal", art, native_batch="force")
        X = np.random.default_rng(3).normal(0, 1, (4, 3, 6, 6)) \
            .astype(np.float32)
        single = np.stack([ex.run(x).output_int8 for x in X])
        np.testing.assert_array_equal(ex.run_batch(X).output_int8, single)
