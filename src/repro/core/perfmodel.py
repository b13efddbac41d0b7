"""Cycle model for the engine (derives the paper's Tables II/III time columns)
plus the kernel cost model that picks a GEMM implementation per descriptor.

Cycle model: max(compute, memory) + configuration-overhead per descriptor:

  compute cycles = MACs / engine.macs
  memory  cycles = bytes moved over the DBB / dbb_bytes_per_cycle
  config  cycles = (#csb writes + #csb polls) * csb_cycles_per_access

The tight coupling + bare-metal claim of the paper shows up here as the config
term: a Linux driver stack pays orders of magnitude more host cycles per op
(syscalls, ioctl marshalling), which is what Table II's comparison against [8]
reflects.  We expose both the raw per-descriptor breakdown and whole-model
totals at the paper's 100 MHz system clock.

Kernel cost model (``select_kernel``): every CONV/FC contraction is lowered to
one of three kernels, chosen per descriptor by estimated cost on the serving
backend — never by a hard-coded size cliff:

  * ``gemm_f32_exact`` — single f32 GEMM; exact only while K*128*128 <= 2^24.
  * ``gemm_f32_tiled`` — K split into <=1024-element tiles, each an exact f32
    GEMM, partials accumulated in int32.  Exact for every K, so the scalar
    integer ``dot_general`` path is never needed.
  * ``pallas_fused``   — the ``kernels/int8_conv`` Pallas kernel: MXU int8
    GEMM with the NVDLA SDP epilogue fused so the int32 accumulator never
    leaves VMEM.

The bf16 (nv_full) datapath has its own candidate family, selected when the
engine config's dtype is ``bf16`` (``KERNELS_BY_DTYPE``):

  * ``gemm_bf16``        — XLA GEMM over bf16 operands, f32 accumulate (bf16
    products are exact in f32, so no K tiling is ever needed).
  * ``pallas_bf16_fused``— the ``kernels/bf16_conv`` Pallas kernel: MXU bf16
    GEMM with the nv_full SDP epilogue (f32 bias + ReLU) fused so the f32
    accumulator never leaves VMEM.

``kernel_plan`` maps a whole descriptor list; the pipeline's ``cost_model``
stage publishes the plan into the ``Artifacts`` manifest.

The cost model is **batch-aware**: ``select_kernel``/``kernel_plan`` take the
coalesced bucket size and compare, per kernel, executing the bucket as N
vmapped single-image launches (weights stream from HBM once *per lane*)
against one natively batched launch that folds the lanes into the GEMM's N
axis (weights stream **once**, amortised over every lane).  The winning
execution style is recorded as ``KernelChoice.batched`` and drives the
executors' batched replay — ``batched_kernel_plans`` publishes the
per-(layer, bucket) plans for the whole coalescing ladder into the manifest.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Union

import numpy as np

from repro.core import engine

# ---------------------------------------------------------------------------
# Kernel selection
# ---------------------------------------------------------------------------
KERNEL_GEMM_EXACT = "gemm_f32_exact"
KERNEL_GEMM_TILED = "gemm_f32_tiled"
KERNEL_PALLAS = "pallas_fused"
KERNEL_VPU = "vpu"                     # PDP / EW: no GEMM, pure vector ops

GEMM_KERNELS = (KERNEL_GEMM_EXACT, KERNEL_GEMM_TILED, KERNEL_PALLAS)

# bf16 (nv_full) kernel family: float accumulate, no requant, no exactness
# tiling (f32 accumulation of exact bf16 products needs no K split)
KERNEL_GEMM_BF16 = "gemm_bf16"         # XLA bf16 GEMM, f32 accumulate
KERNEL_PALLAS_BF16 = "pallas_bf16_fused"

BF16_KERNELS = (KERNEL_GEMM_BF16, KERNEL_PALLAS_BF16)

# which GEMM kernels may serve a descriptor, per engine dtype — selection and
# ``kernel_plan=`` override validation both consult this
KERNELS_BY_DTYPE = {"int8": GEMM_KERNELS, "bf16": BF16_KERNELS}

# Largest contraction K for which a single f32 GEMM is provably bit-exact:
# every int8*int8 product has |p| <= 128*128, so the worst-case partial sum
# K * 128 * 128 must stay within the 2^24 f32 integer-exact window.
EXACT_K = (1 << 24) // (128 * 128)     # = 1024


def bucket_ladder(max_batch: int) -> tuple:
    """The power-of-two coalescing bucket ladder for a ``max_batch`` ceiling.

    Rungs are 1, 2, 4, ... doubling below ``max_batch``, and ``max_batch``
    itself is always the top rung (a non-power-of-two ceiling still gets a
    bucket, matching the scheduler's padded-shape cap).  This is the ONE
    source of truth for which batch shapes exist: ``SchedulerConfig.buckets``
    defaults to it, ``Session.warmup`` precompiles it, and
    ``batched_kernel_plans`` publishes a plan per rung into the manifest.
    """
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    rungs = []
    b = 1
    while b < max_batch:
        rungs.append(b)
        b *= 2
    rungs.append(int(max_batch))
    return tuple(rungs)


# ladder used for manifest publication when no scheduler config is in scope
# (the serving default: scheduler buckets cap at the executor's batch ceiling)
DEFAULT_BUCKET_LADDER = bucket_ladder(32)


@dataclasses.dataclass(frozen=True)
class BackendProfile:
    """What the serving substrate can do, for the kernel cost model.

    Rates are relative (MACs and bytes per cycle) — only ratios matter for
    selection.  The scalar integer GEMM XLA falls back to on CPU is
    deliberately *not* a candidate: ``gemm_f32_tiled`` is exact for every K,
    wins outright whenever the GEMM is compute-bound (output positions /
    coalesced lanes widen the N dimension), and stays within a small
    constant of int8 streaming in the weight-bandwidth-bound GEMV regime.
    """
    platform: str
    f32_macs_per_cycle: float          # wide f32 units (SIMD FMA / MXU f32)
    bytes_per_cycle: float             # weight-stream bandwidth
    pallas_native: bool                # Pallas runs compiled (TPU) vs interpret
    tile_overhead_macs: float = 4096.0  # int32 partial-sum add per extra K-tile
    bf16_macs_per_cycle: float = 0.0   # native bf16 MAC rate (0 = cast to f32)
    launch_overhead_macs: float = 8192.0  # fixed dispatch cost per kernel
                                       # launch (MAC-equivalents) — this is
                                       # the per-lane tax a vmapped bucket
                                       # pays N times and a native-batch
                                       # launch pays once
    vmap_folds: bool = False           # XLA's vmap batching rule already
                                       # folds a broadcast-weight dot_general
                                       # into ONE batched GEMM inside one
                                       # executable (measured parity on CPU),
                                       # so a vmapped bucket pays the weight
                                       # stream and launch once, not per
                                       # lane.  False for the Pallas TPU
                                       # path, where each lane's program
                                       # really does re-stream weights.

    @property
    def bf16_rate(self) -> float:
        """Effective bf16 MAC rate: native when the substrate has bf16 units
        (TPU MXU runs bf16 at 2x the f32 rate), else the f32 units after an
        upcast."""
        return self.bf16_macs_per_cycle or self.f32_macs_per_cycle


PROFILES: Dict[str, BackendProfile] = {
    "cpu": BackendProfile(platform="cpu", f32_macs_per_cycle=16.0,
                          bytes_per_cycle=32.0, pallas_native=False,
                          vmap_folds=True),
    "tpu": BackendProfile(platform="tpu", f32_macs_per_cycle=256.0,
                          bytes_per_cycle=512.0, pallas_native=True,
                          bf16_macs_per_cycle=512.0),
    "gpu": BackendProfile(platform="gpu", f32_macs_per_cycle=128.0,
                          bytes_per_cycle=256.0, pallas_native=False,
                          bf16_macs_per_cycle=256.0, vmap_folds=True),
}


def default_backend() -> str:
    """The profile name for the platform jax will execute on.  A platform
    with no profile raises: costing it as another platform would pick its
    kernels for hardware it does not have."""
    import jax
    plat = jax.default_backend()
    if plat not in PROFILES:
        raise ValueError(f"no backend profile for platform {plat!r}; known: "
                         f"{', '.join(sorted(PROFILES))}")
    return plat


def resolve_profile(backend: Union[str, BackendProfile, None]) -> BackendProfile:
    if backend is None:
        return PROFILES[default_backend()]
    if isinstance(backend, BackendProfile):
        return backend
    try:
        return PROFILES[backend]
    except KeyError:
        raise ValueError(f"unknown backend profile {backend!r}; known: "
                         f"{', '.join(sorted(PROFILES))}") from None


@dataclasses.dataclass(frozen=True)
class KernelChoice:
    """One descriptor's resolved kernel: what runs, and why.

    ``batch`` is the coalesced bucket size the choice was made for;
    ``batched`` says the kernel should run as ONE natively batched launch
    (lanes folded into the GEMM N axis, weights streamed once) rather than
    ``batch`` vmapped single-image launches.
    """
    kernel: str
    contract_k: int = 0
    k_tiles: int = 1
    reason: str = ""
    batch: int = 1
    batched: bool = False

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)


def contract_k(d: engine.Descriptor) -> int:
    """Contraction length K of a CONV/FC descriptor (0 for PDP/EW)."""
    _, c, h, w = d.src_dims
    if d.unit == "CONV":
        r, s = d.kernel
        return (c // d.groups) * r * s
    if d.unit == "FC":
        return c * h * w
    return 0


def gemm_cols(d: engine.Descriptor) -> int:
    """N dimension of the descriptor's GEMM: output positions P*Q (1 for FC)."""
    _, _, p, q = d.dst_dims
    return p * q if d.unit == "CONV" else 1


def descriptor_macs(d: engine.Descriptor) -> int:
    _, c, h, w = d.src_dims
    _, k, p, q = d.dst_dims
    if d.unit == "CONV":
        r, s = d.kernel
        return (c // d.groups) * r * s * k * p * q
    if d.unit == "FC":
        return c * h * w * k
    return 0


def _kernel_cost(kernel: str, k: int, macs: int, n_cols: int,
                 prof: BackendProfile, batch: int = 1,
                 native: bool = False) -> float:
    """Estimated cost (relative cycles) of serving a ``batch``-lane bucket
    with ``kernel`` on ``prof``; ``inf`` when the kernel is not applicable.

    max(compute, weight-stream) roofline: ``n_cols`` (output positions, or
    positions x coalesced lanes) decides which side binds — GEMV-shaped
    layers (n_cols ~ 1) are weight-bandwidth-bound, so the f32 kernels pay
    their 4-byte weight stream there, while wide GEMMs are compute-bound
    and the f32 units win on rate.

    ``native=False`` models ``batch`` vmapped single-image launches: the
    weight stream and the fixed launch overhead are paid once per lane.
    ``native=True`` models ONE batched launch with the lanes folded into the
    GEMM N axis: compute scales with the lanes but the weight stream and the
    launch overhead are paid once — the amortisation the batched kernels buy.

    On ``vmap_folds`` substrates (XLA CPU/GPU) the vmapped style pays the
    stream and launch once too: XLA's batching rule turns the broadcast-weight
    dot_general into a single batched GEMM inside one executable, so vmapping
    already IS the fold there (measured bit-exact parity on CPU) and native
    batching ties rather than wins.
    """
    lanes = max(batch, 1)
    n_tiles = -(-k // EXACT_K) if k else 1
    weight_elems = macs // max(n_cols, 1)
    folded = native or prof.vmap_folds
    streams = 1 if folded else lanes       # weight-stream trips over HBM
    launch = ((1 if folded else lanes)
              * prof.launch_overhead_macs / prof.f32_macs_per_cycle)
    cmacs = lanes * macs
    # the extra-K-tile partial-sum adds cover every output column, so they
    # scale with the lanes under either execution style
    tiles = (n_tiles - 1) * prof.tile_overhead_macs * lanes
    if kernel == KERNEL_GEMM_EXACT:
        if k > EXACT_K:
            return float("inf")            # would break the exactness proof
        return max(cmacs / prof.f32_macs_per_cycle,
                   4.0 * streams * weight_elems / prof.bytes_per_cycle) + launch
    if kernel == KERNEL_GEMM_TILED:
        return (max(cmacs / prof.f32_macs_per_cycle,
                    4.0 * streams * weight_elems / prof.bytes_per_cycle)
                + tiles + launch)
    if kernel == KERNEL_PALLAS:
        if not prof.pallas_native:
            return float("inf")            # interpret mode: test-only on CPU
        # int8 weight stream + fused epilogue (the int32 accumulator stays
        # in VMEM): both sides of the roofline are cheaper than f32
        return max(0.9 * cmacs / prof.f32_macs_per_cycle,
                   1.0 * streams * weight_elems / prof.bytes_per_cycle) + launch
    if kernel == KERNEL_GEMM_BF16:
        # bf16 operands stream at 2 bytes/elem; accumulate rides the bf16
        # units when they exist, the f32 units after an upcast otherwise.
        # The f32 accumulator leaves the GEMM and is read back for the
        # bias/ReLU/cast epilogue: 8 bytes per output element that the fused
        # kernel keeps in VMEM.
        acc_bytes = 8.0 * lanes * n_cols * (weight_elems // max(k, 1))
        return max(cmacs / prof.bf16_rate,
                   (2.0 * streams * weight_elems + acc_bytes)
                   / prof.bytes_per_cycle) + launch
    if kernel == KERNEL_PALLAS_BF16:
        if not prof.pallas_native:
            return float("inf")            # interpret mode: test-only on CPU
        # fused epilogue: the f32 accumulator never leaves VMEM
        return max(0.9 * cmacs / prof.bf16_rate,
                   2.0 * streams * weight_elems / prof.bytes_per_cycle) + launch
    raise ValueError(f"unknown kernel {kernel!r}")


def select_kernel(d: engine.Descriptor,
                  backend: Union[str, BackendProfile, None] = None,
                  override: Optional[str] = None,
                  dtype: str = "int8", batch: int = 1,
                  calibration: Optional["CalibrationProfile"] = None
                  ) -> KernelChoice:
    """Pick the cheapest applicable kernel for one descriptor.

    ``dtype`` is the engine datapath (``EngineConfig.dtype``): it decides the
    candidate set — int8 descriptors resolve to the bit-exact integer GEMMs,
    bf16 (nv_full) descriptors to the f32-accumulate family.  ``override``
    forces a specific GEMM kernel (debugging / A-B testing); forcing
    ``gemm_f32_exact`` on a contraction too large for the exactness bound, or
    a kernel from the wrong dtype family, raises rather than silently
    producing wrong bits.

    ``batch`` is the coalesced bucket size.  For ``batch > 1`` every
    candidate is costed under both execution styles — ``batch`` vmapped
    single-image launches vs one natively batched launch with the lanes
    folded into the GEMM N axis — and the winner's style is recorded in
    ``KernelChoice.batched``.  Native batching must *strictly* beat vmapping
    to be selected: on ``vmap_folds`` substrates (XLA CPU/GPU) the two styles
    cost the same, so the vmapped oracle keeps serving there and ``batched``
    only turns on where the amortisation is real (the Pallas TPU path).  An
    ``override`` forces the kernel but the execution style is still
    cost-chosen (every kernel family has a batched variant, so the override
    can never be silently ignored).

    ``calibration`` swaps the a-priori relative-cycle costs for measured
    microseconds: a ``CalibrationProfile`` fitted by ``calibrate()`` from
    per-layer profiling spans predicts each candidate's latency from its
    fitted per-family constants (compute rate, weight-stream bandwidth,
    launch overhead).  Applicability is still decided by the static model —
    a kernel the static model rules out (exactness bound, interpret-only
    Pallas) stays out no matter what the fit says.
    """
    lanes = max(int(batch), 1)
    if d.unit not in ("CONV", "FC"):
        return KernelChoice(kernel=KERNEL_VPU, reason="no contraction",
                            batch=lanes)
    try:
        candidates = KERNELS_BY_DTYPE[dtype]
    except KeyError:
        raise ValueError(f"no kernel family for engine dtype {dtype!r}; "
                         f"known: {', '.join(sorted(KERNELS_BY_DTYPE))}") \
            from None
    prof = resolve_profile(backend)
    k = contract_k(d)
    macs = descriptor_macs(d)
    n_cols = gemm_cols(d)
    n_tiles = (-(-k // EXACT_K) if k else 1) if dtype == "int8" else 1

    def style_cost(name: str, native: bool) -> float:
        static = _kernel_cost(name, k, macs, n_cols, prof, lanes,
                              native=native)
        if calibration is None or static == float("inf"):
            return static
        eb = 1 if dtype == "int8" else 2
        wbytes = (macs // max(n_cols, 1)) * eb
        us = calibration.predict_us(name, macs, wbytes, batch=lanes,
                                    native=native, static_cost=static)
        return us if us is not None else static

    def exec_style(name: str) -> tuple:
        """(best cost, native-batch wins) for one candidate kernel."""
        vmapped = style_cost(name, native=False)
        if lanes == 1:
            return vmapped, False
        fused = style_cost(name, native=True)
        return min(vmapped, fused), fused < vmapped

    if override is not None:
        if override not in candidates:
            raise ValueError(
                f"unknown kernel {override!r} for dtype {dtype!r}; "
                f"{dtype} GEMM kernels: {', '.join(candidates)}")
        if override == KERNEL_GEMM_EXACT and k > EXACT_K:
            raise ValueError(
                f"kernel {override!r} forced for K={k} > {EXACT_K}: a single "
                f"f32 GEMM is not bit-exact past K*128*128 = 2^24")
        _, native = exec_style(override)
        return KernelChoice(kernel=override, contract_k=k, k_tiles=n_tiles,
                            batch=lanes, batched=native,
                            reason="forced by kernel_plan override")
    styles = {name: exec_style(name) for name in candidates}
    costs = {name: c for name, (c, _) in styles.items()}
    best = min(costs, key=costs.get)
    model = "calibrated cost model" if calibration is not None else "cost model"
    return KernelChoice(
        kernel=best, contract_k=k, k_tiles=n_tiles,
        batch=lanes, batched=styles[best][1],
        reason=f"{model} on {prof.platform} (batch={lanes}): " + ", ".join(
            f"{n}={c:.0f}" if c != float("inf") else f"{n}=n/a"
            for n, c in costs.items()))


def kernel_plan(descs: Sequence[engine.Descriptor],
                names: Optional[Sequence[str]] = None,
                backend: Union[str, BackendProfile, None] = None,
                override: Optional[str] = None,
                dtype: str = "int8", batch: int = 1,
                calibration: Optional["CalibrationProfile"] = None
                ) -> List[Dict]:
    """Per-descriptor kernel plan, as JSON-ready dicts (manifest format)."""
    names = names or [f"op{i}" for i in range(len(descs))]
    prof = resolve_profile(backend)
    out = []
    for d, n in zip(descs, names):
        ch = select_kernel(d, prof, override=override, dtype=dtype,
                           batch=batch, calibration=calibration)
        e = ch.to_dict()
        e.update(layer=n, unit=d.unit, backend=prof.platform, dtype=dtype)
        out.append(e)
    return out


def batched_kernel_plans(descs: Sequence[engine.Descriptor],
                         names: Optional[Sequence[str]] = None,
                         backend: Union[str, BackendProfile, None] = None,
                         override: Optional[str] = None,
                         dtype: str = "int8",
                         buckets: Sequence[int] = DEFAULT_BUCKET_LADDER
                         ) -> Dict[int, List[Dict]]:
    """Per-(layer, bucket) kernel plans for the coalescing ladder.

    ``{bucket: kernel_plan entries}`` for every ladder rung above 1 (the
    1-lane plan is the base ``kernel_plan``); this is what the pipeline
    publishes into the manifest as ``batched_kernel_plans``.
    """
    return {int(b): kernel_plan(descs, names, backend, override=override,
                                dtype=dtype, batch=int(b))
            for b in buckets if int(b) > 1}


# ---------------------------------------------------------------------------
# Measured calibration: fit the cost model's constants from per-layer spans
# ---------------------------------------------------------------------------
def sample_features(d: engine.Descriptor, dtype: str = "int8") -> tuple:
    """(MAC-equivalents, streamed bytes) of one descriptor — the two features
    the calibration fit regresses measured microseconds against.

    CONV/FC stream their weight matrix (the roofline's bandwidth side); the
    vector units (PDP/EW) have no weights, so their "stream" is the
    activation traffic — the fitted bandwidth constant absorbs the
    difference in what the bytes actually are.
    """
    eb = 1 if dtype == "int8" else 2
    _, c, h, w = d.src_dims
    _, k, p, q = d.dst_dims
    if d.unit in ("CONV", "FC"):
        macs = descriptor_macs(d)
        return macs, (macs // max(gemm_cols(d), 1)) * eb
    if d.unit == "PDP":
        r, s = d.kernel
        return k * p * q * r * s, c * h * w * eb + k * p * q * eb
    if d.unit == "EW":
        return k * p * q * 2, 2 * c * h * w * eb + k * p * q * eb
    raise ValueError(d.unit)


def static_cost_units(d: engine.Descriptor, kernel: str,
                      backend: Union[str, BackendProfile, None] = None,
                      dtype: str = "int8", batch: int = 1,
                      native: bool = False) -> float:
    """A-priori cost (relative cycles) of one descriptor under ``kernel`` —
    the uncalibrated model the fidelity report compares measurements against.
    GEMM kernels use ``_kernel_cost``; the vector units use their own
    roofline over ``sample_features`` (they have no GEMM kernel entry)."""
    prof = resolve_profile(backend)
    if d.unit in ("CONV", "FC"):
        return _kernel_cost(kernel, contract_k(d), descriptor_macs(d),
                            gemm_cols(d), prof, batch, native)
    macs, sbytes = sample_features(d, dtype)
    lanes = max(batch, 1)
    return lanes * max(macs / prof.f32_macs_per_cycle,
                       sbytes / prof.bytes_per_cycle)


@dataclasses.dataclass(frozen=True)
class CalibrationProfile:
    """Measured per-kernel-family cost constants, fitted by ``calibrate()``.

    ``families[kernel]`` holds the fitted additive model in microseconds:

        us = lanes*macs * us_per_mac
           + streams*bytes * us_per_byte        (streams = 1 when folded)
           + launches * launch_us               (launches = 1 when folded)

    (reciprocals of the paper-facing "compute rate" / "weight-stream
    bandwidth"; ``compute_rate``/``stream_bw`` expose those directly).
    ``us_per_cycle`` is the global scale fallback — measured microseconds per
    modeled relative cycle — used for kernel families the profiling run never
    exercised, so a calibrated ``select_kernel`` still compares every
    candidate in the same (microsecond) unit.
    """
    platform: str
    dtype: str = "int8"
    vmap_folds: bool = True
    us_per_cycle: float = 0.0
    families: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=dict)
    samples: int = 0

    def compute_rate(self, kernel: str) -> float:
        """Fitted compute rate in MACs/us (0 when unfitted/unbounded)."""
        f = self.families.get(kernel)
        return 1.0 / f["us_per_mac"] if f and f["us_per_mac"] > 0 else 0.0

    def stream_bw(self, kernel: str) -> float:
        """Fitted stream bandwidth in bytes/us (0 when unfitted/unbounded)."""
        f = self.families.get(kernel)
        return 1.0 / f["us_per_byte"] if f and f["us_per_byte"] > 0 else 0.0

    def launch_us(self, kernel: str) -> float:
        f = self.families.get(kernel)
        return f["launch_us"] if f else 0.0

    def predict_us(self, kernel: str, macs: float, stream_bytes: float,
                   batch: int = 1, native: bool = False,
                   static_cost: Optional[float] = None) -> Optional[float]:
        """Predicted latency in microseconds, or ``None`` when the family is
        unfitted and no fallback is possible."""
        lanes = max(int(batch), 1)
        folded = native or self.vmap_folds
        f = self.families.get(kernel)
        if f is not None:
            streams = 1 if folded else lanes
            return (lanes * macs * f["us_per_mac"]
                    + streams * stream_bytes * f["us_per_byte"]
                    + streams * f["launch_us"])
        if static_cost is not None and self.us_per_cycle > 0:
            return static_cost * self.us_per_cycle
        return None

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, doc: Dict) -> "CalibrationProfile":
        return cls(platform=doc["platform"], dtype=doc.get("dtype", "int8"),
                   vmap_folds=bool(doc.get("vmap_folds", True)),
                   us_per_cycle=float(doc.get("us_per_cycle", 0.0)),
                   families={k: dict(v)
                             for k, v in doc.get("families", {}).items()},
                   samples=int(doc.get("samples", 0)))


def _fit_family(rows: List[tuple]) -> Optional[Dict[str, float]]:
    """Nonnegative least squares over (cmacs, sbytes, launches) -> us.

    Plain lstsq with iterative column dropping: a negative coefficient means
    that feature is colinear with another on this sample set (tiny nets often
    can't separate bandwidth from compute), so the offending column is
    removed and the rest refitted rather than shipping a negative "rate"."""
    A = np.array([[r[0], r[1], r[2]] for r in rows], dtype=np.float64)
    b = np.array([r[3] for r in rows], dtype=np.float64)
    cols = [0, 1, 2]
    while True:
        coef, *_ = np.linalg.lstsq(A[:, cols], b, rcond=None)
        neg = [j for j, c in enumerate(coef) if c < 0]
        if not neg or len(cols) <= 1:
            break
        cols = [c for j, c in enumerate(cols) if j not in neg]
    full = [0.0, 0.0, 0.0]
    for j, c in zip(cols, coef):
        full[j] = max(float(c), 0.0)
    if not all(np.isfinite(full)) or sum(full) <= 0:
        # degenerate fit (e.g. a single repeated layer): scale-only model
        cm = np.array([r[0] for r in rows], dtype=np.float64)
        if cm.sum() <= 0:
            return None
        full = [float(np.median(b[cm > 0] / cm[cm > 0])), 0.0, 0.0]
    return {"us_per_mac": full[0], "us_per_byte": full[1],
            "launch_us": full[2], "samples": float(len(rows))}


def calibrate(samples: Sequence[Dict],
              descs: Sequence[engine.Descriptor],
              backend: Union[str, BackendProfile, None] = None,
              dtype: str = "int8") -> CalibrationProfile:
    """Fit a ``CalibrationProfile`` from measured per-layer profiling samples.

    ``samples`` are the dicts the executors' ``run_profiled`` emits
    (``obs.report.profile_layers``): ``{"index", "kernel", "us"}`` plus
    optional ``bucket`` (coalesced lanes, default 1) and ``native``
    (batched-launch style).
    Constants are fitted per kernel family; the global ``us_per_cycle``
    scale comes from the median measured/modeled ratio across every sample,
    so families the run never exercised still predict in microseconds.
    """
    prof = resolve_profile(backend)
    by_family: Dict[str, List[tuple]] = {}
    ratios = []
    n_used = 0
    for s in samples:
        idx = int(s["index"])
        if not 0 <= idx < len(descs):
            continue
        d = descs[idx]
        us = float(s["us"])
        if us <= 0:
            continue
        kernel = s.get("kernel") or KERNEL_VPU
        lanes = max(int(s.get("bucket", 1)), 1)
        native = bool(s.get("native", False))
        folded = native or prof.vmap_folds
        macs, sbytes = sample_features(d, dtype)
        streams = 1 if folded else lanes
        by_family.setdefault(kernel, []).append(
            (lanes * macs, streams * sbytes, streams, us))
        static = static_cost_units(d, kernel, prof, dtype, lanes, native)
        if np.isfinite(static) and static > 0:
            ratios.append(us / static)
        n_used += 1
    families = {}
    for kernel, rows in by_family.items():
        fit = _fit_family(rows)
        if fit is not None:
            families[kernel] = fit
    return CalibrationProfile(
        platform=prof.platform, dtype=dtype, vmap_folds=prof.vmap_folds,
        us_per_cycle=float(np.median(ratios)) if ratios else 0.0,
        families=families, samples=n_used)


@dataclasses.dataclass
class OpCost:
    layer: str
    unit: str
    macs: int
    bytes_moved: int
    compute_cycles: int
    memory_cycles: int
    config_cycles: int

    @property
    def cycles(self) -> int:
        return max(self.compute_cycles, self.memory_cycles) + self.config_cycles


@dataclasses.dataclass
class ModelCost:
    ops: List[OpCost]
    total_cycles: int
    ms_at_clock: float
    kernel_plan: Optional[List[Dict]] = None   # per-layer kernel choice dicts
    batched_kernel_plans: Optional[Dict[int, List[Dict]]] = None
                                               # per-(layer, bucket) choices

    def layer_breakdown(self) -> List[Dict]:
        """Per-layer time share + chosen kernel, sorted by modeled cycles."""
        total = max(self.total_cycles, 1)
        plan = {e["layer"]: e for e in (self.kernel_plan or [])}
        rows = []
        for o in self.ops:
            ch = plan.get(o.layer, {})
            rows.append({
                "layer": o.layer, "unit": o.unit, "cycles": o.cycles,
                "share": o.cycles / total,
                "kernel": ch.get("kernel", ""),
                "contract_k": ch.get("contract_k", 0),
                "k_tiles": ch.get("k_tiles", 1),
            })
        rows.sort(key=lambda r: -r["cycles"])
        return rows

    def dominant(self) -> str:
        c = sum(o.compute_cycles for o in self.ops)
        m = sum(o.memory_cycles for o in self.ops)
        g = sum(o.config_cycles for o in self.ops)
        return max(("compute", c), ("memory", m), ("config", g), key=lambda t: t[1])[0]


def descriptor_cost(d: engine.Descriptor, cfg: engine.EngineConfig,
                    name: str = "") -> OpCost:
    _, c, h, w = d.src_dims
    _, k, p, q = d.dst_dims
    eb = cfg.elem_bytes
    if d.unit == "CONV":
        r, s = d.kernel
        macs = descriptor_macs(d)
        wbytes = k * (c // d.groups) * r * s * eb
        bytes_moved = c * h * w * eb + wbytes + k * 4 * 2 + k * p * q * eb
    elif d.unit == "FC":
        cin = c * h * w
        macs = descriptor_macs(d)
        bytes_moved = cin * eb + k * cin * eb + k * 4 * 2 + k * eb
    elif d.unit == "PDP":
        r, s = d.kernel
        macs = k * p * q * r * s          # adds count as MAC-equivalent work
        bytes_moved = c * h * w * eb + k * p * q * eb
    elif d.unit == "EW":
        macs = k * p * q * 2
        bytes_moved = 2 * c * h * w * eb + k * p * q * eb
    else:
        raise ValueError(d.unit)
    n_writes = len(d.to_reg_writes()) + 1     # + STATUS poll
    return OpCost(
        layer=name, unit=d.unit, macs=macs, bytes_moved=bytes_moved,
        compute_cycles=int(np.ceil(macs / (cfg.macs * cfg.mac_util))),
        memory_cycles=int(np.ceil(bytes_moved / (cfg.dbb_bytes_per_cycle * cfg.dbb_eff))),
        config_cycles=n_writes * cfg.csb_cycles_per_access + cfg.op_overhead_cycles,
    )


def model_cost(descs: List[engine.Descriptor], cfg: engine.EngineConfig,
               names: List[str] | None = None,
               backend: Union[str, BackendProfile, None] = None) -> ModelCost:
    names = names or [f"op{i}" for i in range(len(descs))]
    ops = [descriptor_cost(d, cfg, n) for d, n in zip(descs, names)]
    total = sum(o.cycles for o in ops)
    return ModelCost(ops=ops, total_cycles=total,
                     ms_at_clock=cfg.cycles_to_ms(total),
                     kernel_plan=kernel_plan(descs, names, backend,
                                             dtype=cfg.dtype),
                     batched_kernel_plans=batched_kernel_plans(
                         descs, names, backend, dtype=cfg.dtype))
