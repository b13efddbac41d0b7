"""Natively batched kernels: ladder-wide parity, warmup, bucket config.

Four layers of guarantees for the one-launch-per-bucket path:
  * kernel-level parity on EVERY rung of the coalescing ladder: the batched
    int8 Pallas kernels (interpret mode) are bit-identical to per-lane
    refops — including dead-lane zero padding, groups, stride, pad and FC —
    and the bf16 twins are bit-identical to vmapping the single-image
    kernel (tolerance-bounded only vs the differently-ordered refops),
  * executor-level: ``native_batch="force"`` (one fused launch per bucket)
    matches the vmapped oracle and sequential ``run`` byte-for-byte on both
    the int8 and the bf16 datapaths,
  * a warmed ``Session`` serves every ladder bucket shape with ZERO new
    compilations — the invariant the warmup tentpole exists to enforce,
  * mis-shaped bucket ladders fail at ``SchedulerConfig`` construction with
    a descriptive error, and the serve front door refuses traffic (503
    ``warming``) while warmup runs.
"""

import numpy as np
import jax
import jax.numpy as jnp
import ml_dtypes
import pytest

from repro.core import engine, graph, perfmodel, quant, refops
from repro.core.pipeline import CompilerPipeline
from repro.core.tolerances import assert_close, gemm_tolerance
from repro.kernels.int8_conv.ops import conv2d_int8_batch, fc_int8_batch
from repro.kernels.bf16_conv.ops import (conv2d_bf16, conv2d_bf16_batch,
                                         fc_bf16, fc_bf16_batch)
from repro.runtime import Session, SchedulerConfig, create_executor
from repro.runtime.scheduler import SchedulerConfig as SchedCfg
from repro.serve.client import ServeClient, WarmingUpError

LADDER = perfmodel.DEFAULT_BUCKET_LADDER          # (1, 2, 4, 8, 16, 32)


def _words(rng, n, max_acc):
    return np.array([quant.pack_scale(*quant.fixed_point(s, max_acc))
                     for s in rng.uniform(1e-5, 1e-3, n)], dtype=np.uint32)


# tiny-but-representative conv shapes; one case per satellite requirement
CONV_CASES = {
    "plain":   dict(cin=3, h=6, cout=4, k=3, stride=1, pad=0, groups=1,
                    relu=True),
    "pad":     dict(cin=2, h=5, cout=4, k=3, stride=1, pad=1, groups=1,
                    relu=False),
    "stride2": dict(cin=3, h=7, cout=4, k=3, stride=2, pad=1, groups=1,
                    relu=True),
    "groups2": dict(cin=4, h=6, cout=6, k=3, stride=1, pad=0, groups=2,
                    relu=True),
}


def _conv_inputs(case, bucket, seed=0):
    c = CONV_CASES[case]
    cin_g = c["cin"] // c["groups"]
    kdim = cin_g * c["k"] * c["k"]
    rng = np.random.default_rng(seed + bucket)
    xs = rng.integers(-128, 128, (bucket, c["cin"], c["h"], c["h"]),
                      dtype=np.int8)
    wq = rng.integers(-128, 128, (c["cout"], kdim), dtype=np.int8)
    bias = rng.integers(-1000, 1000, c["cout"], dtype=np.int32)
    words = _words(rng, c["cout"], kdim * 128 * 128)
    return c, xs, wq, bias, words


# ---------------------------------------------------------------------------
# Kernel-level parity on every ladder bucket (interpret-mode Pallas)
# ---------------------------------------------------------------------------
class TestInt8BatchKernelParity:
    @pytest.mark.parametrize("bucket", LADDER)
    @pytest.mark.parametrize("case", sorted(CONV_CASES))
    def test_conv_bit_exact_vs_refops_per_lane(self, case, bucket):
        c, xs, wq, bias, words = _conv_inputs(case, bucket)
        got = conv2d_int8_batch(
            jnp.asarray(xs), jnp.asarray(wq), jnp.asarray(bias),
            jnp.asarray(words.view(np.int32)), c["k"], c["stride"],
            c["pad"], c["groups"], c["relu"], interpret=True)
        want = np.stack([refops.conv_int8(x, wq, bias, words, c["k"],
                                          c["stride"], c["pad"], c["groups"],
                                          c["relu"]) for x in xs])
        np.testing.assert_array_equal(np.asarray(got), want)

    @pytest.mark.parametrize("bucket", LADDER)
    def test_fc_bit_exact_vs_refops_per_lane(self, bucket):
        cin, cout = 18, 5
        rng = np.random.default_rng(bucket)
        xs = rng.integers(-128, 128, (bucket, cin), dtype=np.int8)
        wq = rng.integers(-128, 128, (cout, cin), dtype=np.int8)
        bias = rng.integers(-1000, 1000, cout, dtype=np.int32)
        words = _words(rng, cout, cin * 128 * 128)
        got = fc_int8_batch(jnp.asarray(xs), jnp.asarray(wq),
                            jnp.asarray(bias),
                            jnp.asarray(words.view(np.int32)), relu=True,
                            interpret=True)
        want = np.stack([refops.fc_int8(x, wq, bias, words, relu=True)
                         for x in xs])
        np.testing.assert_array_equal(np.asarray(got), want)

    def test_dead_lane_padding_is_inert(self):
        # a coalesced dispatch pads the bucket with zero lanes; the fold
        # must keep live lanes bit-exact AND compute the padded lanes as
        # honest zero-input inferences (they are sliced off downstream)
        bucket, live = 8, 5
        c, xs, wq, bias, words = _conv_inputs("plain", live, seed=7)
        padded = np.zeros((bucket,) + xs.shape[1:], dtype=np.int8)
        padded[:live] = xs
        got = np.asarray(conv2d_int8_batch(
            jnp.asarray(padded), jnp.asarray(wq), jnp.asarray(bias),
            jnp.asarray(words.view(np.int32)), c["k"], c["stride"],
            c["pad"], c["groups"], c["relu"], interpret=True))
        want_live = np.stack([refops.conv_int8(x, wq, bias, words, c["k"],
                                               c["stride"], c["pad"],
                                               c["groups"], c["relu"])
                              for x in xs])
        np.testing.assert_array_equal(got[:live], want_live)
        want_dead = refops.conv_int8(np.zeros_like(xs[0]), wq, bias, words,
                                     c["k"], c["stride"], c["pad"],
                                     c["groups"], c["relu"])
        for lane in range(live, bucket):
            np.testing.assert_array_equal(got[lane], want_dead)


class TestBf16BatchKernelParity:
    @pytest.mark.parametrize("bucket", LADDER)
    def test_conv_matches_vmapped_kernel_and_refops(self, bucket):
        cin, h, cout, k = 3, 6, 4, 3
        rng = np.random.default_rng(bucket)
        xs = rng.normal(0, 1, (bucket, cin, h, h)).astype(ml_dtypes.bfloat16)
        wq = rng.normal(0, 0.5, (cout, cin * k * k)).astype(ml_dtypes.bfloat16)
        bias = rng.normal(0, 1, cout).astype(np.float32)
        got = np.asarray(conv2d_bf16_batch(
            jnp.asarray(xs), jnp.asarray(wq), jnp.asarray(bias),
            k, 1, 0, relu=True, interpret=True), np.float32)
        # folding lanes onto the GEMM N axis preserves each column's f32
        # accumulation order -> bit-identical to vmapping the image kernel
        vmapped = np.asarray(jax.vmap(
            lambda x: conv2d_bf16(x, jnp.asarray(wq), jnp.asarray(bias),
                                  k, 1, 0, relu=True, interpret=True))(
                jnp.asarray(xs)),
            np.float32)
        np.testing.assert_array_equal(got, vmapped)
        want = np.stack([refops.conv_bf16(x, wq, bias, k, 1, 0, relu=True)
                         for x in xs])
        assert_close(got, want, gemm_tolerance(cin * k * k),
                     f"conv_bf16_batch bucket={bucket}")

    @pytest.mark.parametrize("bucket", (1, 8, 32))
    def test_fc_matches_vmapped_kernel_and_refops(self, bucket):
        cin, cout = 18, 5
        rng = np.random.default_rng(bucket)
        xs = rng.normal(0, 1, (bucket, cin)).astype(ml_dtypes.bfloat16)
        wq = rng.normal(0, 0.5, (cout, cin)).astype(ml_dtypes.bfloat16)
        bias = rng.normal(0, 1, cout).astype(np.float32)
        got = np.asarray(fc_bf16_batch(jnp.asarray(xs), jnp.asarray(wq),
                                       jnp.asarray(bias), interpret=True),
                         np.float32)
        vmapped = np.asarray(jax.vmap(
            lambda x: fc_bf16(x, jnp.asarray(wq), jnp.asarray(bias),
                              interpret=True))(
                jnp.asarray(xs)), np.float32)
        np.testing.assert_array_equal(got, vmapped)
        want = np.stack([refops.fc_bf16(x, wq, bias) for x in xs])
        assert_close(got, want, gemm_tolerance(cin),
                     f"fc_bf16_batch bucket={bucket}")


# ---------------------------------------------------------------------------
# Batch-aware cost model
# ---------------------------------------------------------------------------
def _conv_desc(kdim: int) -> engine.Descriptor:
    cin = kdim // 9
    return engine.Descriptor(unit="CONV", src_dims=(1, cin, 8, 8),
                             dst_dims=(1, 16, 8, 8), kernel=(3, 3))


class TestBatchAwareSelection:
    def test_bucket_size_is_recorded_on_the_choice(self):
        ch = perfmodel.select_kernel(_conv_desc(576), backend="cpu", batch=16)
        assert ch.batch == 16

    def test_vmap_folds_substrates_keep_the_vmapped_oracle(self):
        # XLA CPU's batching rule already folds broadcast-weight GEMMs into
        # one batched GEMM, so native batching can't win there — the plan
        # must keep serving the vmapped single-image program
        for batch in LADDER:
            ch = perfmodel.select_kernel(_conv_desc(2304), backend="cpu",
                                         batch=batch)
            assert not ch.batched

    def test_tpu_profile_batches_natively_past_one_lane(self):
        # on the Pallas TPU path each vmapped lane really re-streams the
        # weights, so the fold's amortisation is real
        for batch in (2, 8, 32):
            ch = perfmodel.select_kernel(_conv_desc(2304), backend="tpu",
                                         batch=batch)
            assert ch.kernel == perfmodel.KERNEL_PALLAS and ch.batched
        assert not perfmodel.select_kernel(_conv_desc(2304), backend="tpu",
                                           batch=1).batched

    def test_batched_plans_cover_every_ladder_rung(self):
        descs = [_conv_desc(576)]
        plans = perfmodel.batched_kernel_plans(descs, backend="tpu")
        assert set(plans) == set(b for b in LADDER if b > 1)


# ---------------------------------------------------------------------------
# Executor: forced native fold vs vmapped oracle vs sequential
# ---------------------------------------------------------------------------
def _tiny_net():
    g = graph.NetGraph("tiny_batched", (2, 8, 8))
    g.layer(name="data", type="input", inputs=[])
    x = g.layer(name="c1", type="conv", inputs=["data"], out_channels=4,
                kernel=3, pad=1, relu=True)
    x = g.layer(name="p1", type="pool", inputs=[x], pool_mode="gap")
    g.layer(name="fc", type="fc", inputs=[x], out_channels=3)
    return g.infer_shapes()


@pytest.fixture(scope="module")
def tiny_art():
    return CompilerPipeline(_tiny_net()).run()


@pytest.fixture(scope="module")
def nvfull_art():
    return CompilerPipeline(_tiny_net(), cfg=engine.NV_FULL).run()


class TestExecutorNativeBatch:
    def test_force_matches_vmapped_and_sequential_int8(self, tiny_art):
        rng = np.random.default_rng(3)
        X = rng.normal(0, 1, (8, 2, 8, 8)).astype(np.float32)
        ex_f = create_executor("baremetal", tiny_art, native_batch="force")
        ex_v = create_executor("baremetal", tiny_art, native_batch=False)
        forced = np.asarray(ex_f.run_batch(X).output_int8)
        vmapped = np.asarray(ex_v.run_batch(X).output_int8)
        np.testing.assert_array_equal(forced, vmapped)
        seq = np.stack([np.asarray(ex_v.run(x).output_int8) for x in X])
        np.testing.assert_array_equal(forced, seq)

    def test_force_matches_vmapped_bf16_bitwise(self, nvfull_art):
        rng = np.random.default_rng(4)
        X = rng.normal(0, 1, (8, 2, 8, 8)).astype(np.float32)
        ex_f = create_executor("baremetal", nvfull_art, native_batch="force")
        ex_v = create_executor("baremetal", nvfull_art, native_batch=False)
        forced = np.asarray(ex_f.run_batch(X).output, np.float32)
        vmapped = np.asarray(ex_v.run_batch(X).output, np.float32)
        np.testing.assert_array_equal(forced, vmapped)

    def test_bad_native_batch_value_is_rejected(self, tiny_art):
        with pytest.raises(ValueError, match="native_batch"):
            create_executor("baremetal", tiny_art, native_batch="yes")

    @pytest.mark.skipif(jax.default_backend() == "tpu",
                        reason="CPU/GPU plan shape only")
    def test_cpu_plan_keeps_vmapped_oracle(self, tiny_art):
        ex = create_executor("baremetal", tiny_art)
        plan = ex.batched_kernel_plan(8)
        assert not any(ch.batched for ch in plan)


# ---------------------------------------------------------------------------
# Warmup: a warmed Session never compile-stalls a request
# ---------------------------------------------------------------------------
class TestSessionWarmup:
    def test_warmed_session_serves_all_buckets_with_zero_new_compiles(
            self, tiny_art):
        cfg = SchedulerConfig(max_batch=8, max_wait_us=2000.0)
        ses = Session(tiny_art, scheduler=cfg, warmup=True)
        try:
            warm = ses.stats().snapshot()
            assert warm["compile_count"] > 0          # warmup really compiled
            assert warm["warmup_ms"] > 0.0
            rng = np.random.default_rng(5)
            # every ladder bucket shape: singles, a pad-to-4 burst, a full
            # burst, and an explicit run_batch
            ses.run(rng.normal(0, 1, (2, 8, 8)).astype(np.float32))
            for n in (3, 8):
                X = rng.normal(0, 1, (n, 2, 8, 8)).astype(np.float32)
                futs = [ses.submit(x) for x in X]
                for f in futs:
                    f.result(timeout=30)
            ses.run_batch(rng.normal(0, 1, (2, 2, 8, 8)).astype(np.float32))
            snap = ses.stats().snapshot()
            assert snap["compile_count"] == warm["compile_count"], \
                "a request paid a compile stall after warmup"
        finally:
            ses.close()

    def test_warmup_returns_per_net_wall_time(self, tiny_art):
        ses = Session(tiny_art, scheduler=SchedulerConfig(max_batch=2))
        try:
            out = ses.warmup()
            assert set(out) == {"tiny_batched"}
            assert out["tiny_batched"] > 0.0
            assert ses.stats().warmup_ms == pytest.approx(
                out["tiny_batched"])
        finally:
            ses.close()


# ---------------------------------------------------------------------------
# Bucket-ladder config validation (satellite bugfix)
# ---------------------------------------------------------------------------
class TestSchedulerBucketConfig:
    def test_default_ladder_comes_from_perfmodel(self):
        assert SchedCfg(max_batch=8).buckets == perfmodel.bucket_ladder(8)

    def test_non_monotonic_ladder_is_rejected(self):
        with pytest.raises(ValueError, match="strictly increasing"):
            SchedCfg(max_batch=8, buckets=(4, 2, 8))

    def test_rung_past_max_batch_is_rejected(self):
        with pytest.raises(ValueError, match="exceeds"):
            SchedCfg(max_batch=8, buckets=(1, 2, 16))

    def test_non_power_of_two_rung_needs_adaptive_off(self):
        with pytest.raises(ValueError, match="powers of"):
            SchedCfg(max_batch=8, buckets=(1, 3, 8))
        cfg = SchedCfg(max_batch=12, buckets=(1, 3, 12), adaptive=False)
        assert cfg.bucket_for(2) == 3 and cfg.bucket_for(5) == 12

    def test_empty_or_nonpositive_ladder_is_rejected(self):
        with pytest.raises(ValueError, match="non-empty"):
            SchedCfg(max_batch=8, buckets=())
        with pytest.raises(ValueError, match="non-empty"):
            SchedCfg(max_batch=8, buckets=(0, 2))
        with pytest.raises(ValueError, match="max_batch"):
            SchedCfg(max_batch=0)

    def test_bucket_for_rounds_to_smallest_rung(self):
        cfg = SchedCfg(max_batch=8)
        assert [cfg.bucket_for(n) for n in (1, 2, 3, 5, 8)] == [1, 2, 4, 8, 8]


# ---------------------------------------------------------------------------
# Serve front door: 503 "warming" until warmup completes
# ---------------------------------------------------------------------------
class TestServeWarmingGate:
    def test_client_refuses_traffic_while_warming(self, tiny_art):
        ses = Session(tiny_art, scheduler=SchedulerConfig(max_batch=2))
        try:
            client = ServeClient(ses)
            client.begin_warmup()
            assert client.healthz()["status"] == "warming"
            x = np.zeros((2, 8, 8), np.float32)
            with pytest.raises(WarmingUpError) as err:
                client.infer(None, x)
            assert err.value.status == 503 and err.value.code == "warming"
            client.finish_warmup()
            assert client.healthz()["status"] == "ok"
            assert client.infer(None, x).output_int8.shape[0] == 3
        finally:
            ses.close()
