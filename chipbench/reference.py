"""Plain reference of the served CNNs, written apart from the program.

It imports nothing of ``repro`` and takes nothing the program made: the
network is built from the configuration's architecture numbers, the float
weights come from ``make_weights(seed)``, and the activation scales from the
configuration file.  From those it derives, by the NVDLA arithmetic the
configuration states, everything the engine would hold: per-channel int8
weights, int32 biases, fixed-point requantisation words.

Precisions (``forward(..., precision=)``):

  ``int8``  nv_small: int8 weights and activations, int32 accumulation,
            SDP fixed-point requantisation ``((acc >> pre) * m) >> post``
            with round-half-away shifts.  Exact: the int8 products are summed
            in float64, which holds every partial sum of these layers exactly.
  ``int4``  the same datapath with 4-bit weights and activations (the scales
            widened by 127/7) — the control one step below int8.
  ``bf16``  nv_full: bf16 weights and activations, float32 accumulation,
            every layer's output rounded to bf16.
  ``f32``   the float network, unrounded (what calibration measures).

Layout: activations (N, C, H, W); conv weights (K, C/g, R, S).  A conv's
``groups`` splits C and K into that many blocks, block j of the output read
from block j of the input only (``groups == C`` is a depthwise conv).  A
``concat`` joins its inputs along C, in order.
"""

from __future__ import annotations

import numpy as np

INT_RANGE = {"int8": (-128, 127), "int4": (-8, 7)}
M_MAX = (1 << 15) - 1            # SDP multiplier: 16-bit signed


# ---------------------------------------------------------------------------
# Architecture -> layer list
# ---------------------------------------------------------------------------
LAYER_TYPES = ("input", "conv", "fc", "pool", "add", "concat")


def build(arch: dict) -> list:
    """Topologically ordered layers: dicts with ``name``, ``type`` (input,
    conv, fc, pool, add, concat), ``inputs`` and the op's sizes.  Three
    kinds: ``resnet`` (a 7x7/2 stem, a 3x3/2 max pool, stages of bottleneck
    blocks, global average pool, one FC), ``sequential`` (a stated chain)
    and ``graph`` (a stated list in this vocabulary, input layer first,
    checked by ``_check_graph``)."""
    layers = [{"name": "data", "type": "input", "inputs": []}]

    def add(**kw):
        layers.append(kw)
        return kw["name"]

    kind = arch["kind"]
    if kind == "graph":
        return _check_graph([dict(l) for l in arch["layers"]])
    if kind == "sequential":
        prev = "data"
        for spec in arch["layers"]:
            prev = add(inputs=[prev], **spec)
        return layers
    if kind != "resnet":
        raise ValueError(f"unknown architecture kind {kind!r}")
    stem = arch["stem_channels"]
    x = add(name="stem", type="conv", inputs=["data"], out=stem, k=7,
            stride=2, pad=3, relu=True)
    x = add(name="stem_pool", type="pool", inputs=[x], mode="max", k=3,
            stride=2, pad=1)
    cin = stem
    exp = arch["expansion"]
    for si, (cmid, blocks, stride) in enumerate(arch["stages"]):
        for b in range(blocks):
            s = stride if b == 0 else 1
            cout = cmid * exp
            n = f"s{si}b{b}"
            y = add(name=f"{n}_c1", type="conv", inputs=[x], out=cmid, k=1,
                    stride=1, pad=0, relu=True)
            y = add(name=f"{n}_c2", type="conv", inputs=[y], out=cmid, k=3,
                    stride=s, pad=1, relu=True)
            y = add(name=f"{n}_c3", type="conv", inputs=[y], out=cout, k=1,
                    stride=1, pad=0, relu=False)
            sc = x
            if s != 1 or cin != cout:
                sc = add(name=f"{n}_sc", type="conv", inputs=[x], out=cout,
                         k=1, stride=s, pad=0, relu=False)
            x = add(name=f"{n}_add", type="add", inputs=[y, sc], relu=True)
            cin = cout
    x = add(name="gap", type="pool", inputs=[x], mode="gap")
    add(name="fc", type="fc", inputs=[x], out=arch["num_classes"],
        relu=False)
    return layers


def _check_graph(layers: list) -> list:
    """A ``graph`` kind's list, validated: unique names, known types, the
    one input layer first, every input an earlier layer, one input for
    conv, fc and pool, two for add, two or more for concat, and every layer
    but the last read by a later one (the last is the net's output)."""
    def err(msg):
        raise ValueError(f"graph architecture: {msg}")

    if not layers or layers[0]["type"] != "input" or layers[0]["inputs"]:
        err("the list must start with the input layer, which reads nothing")
    seen, unread = set(), set()
    for l in layers:
        name, t, ins = l["name"], l["type"], l["inputs"]
        if name in seen:
            err(f"duplicate layer name {name!r}")
        if t not in LAYER_TYPES or (t == "input" and seen):
            err(f"layer {name!r} has type {t!r}")
        for i in ins:
            if i not in seen:
                err(f"layer {name!r} reads {i!r}, not an earlier layer")
        arity = {"add": len(ins) == 2, "concat": len(ins) >= 2,
                 "input": True}.get(t, len(ins) == 1)
        if not arity:
            err(f"layer {name!r} ({t}) has {len(ins)} inputs")
        seen.add(name)
        unread -= set(ins)
        unread.add(name)
    if unread != {layers[-1]["name"]}:
        err(f"layers {sorted(unread - {layers[-1]['name']})} are read by "
            f"no later layer: the last layer must be the only output")
    return layers


def shapes(layers: list, input_shape) -> dict:
    """name -> (C, H, W) of every layer's output."""
    out = {}
    for l in layers:
        t = l["type"]
        if t == "input":
            out[l["name"]] = tuple(input_shape)
            continue
        if t == "concat":
            ins = [out[i] for i in l["inputs"]]
            if any(o[1:] != ins[0][1:] for o in ins):
                raise ValueError(f"concat {l['name']!r}: inputs of "
                                 f"different H, W {ins}")
            out[l["name"]] = (sum(o[0] for o in ins),) + ins[0][1:]
            continue
        c, h, w = out[l["inputs"][0]]
        if t == "conv" and (c % l.get("groups", 1)
                            or l["out"] % l.get("groups", 1)):
            raise ValueError(f"conv {l['name']!r}: groups "
                             f"{l.get('groups', 1)} must divide C {c} and "
                             f"out {l['out']}")
        if t == "conv" or (t == "pool" and l["mode"] != "gap"):
            k, s, p = l["k"], l.get("stride", 1), l.get("pad", 0)
            oh, ow = (h + 2 * p - k) // s + 1, (w + 2 * p - k) // s + 1
            out[l["name"]] = (l["out"] if t == "conv" else c, oh, ow)
        elif t == "pool":
            out[l["name"]] = (c, 1, 1)
        elif t == "fc":
            out[l["name"]] = (l["out"], 1, 1)
        elif t == "add":
            if out[l["inputs"][1]] != (c, h, w):
                raise ValueError(f"add {l['name']!r}: operands of different"
                                 f" shapes")
            out[l["name"]] = (c, h, w)
        else:
            raise ValueError(t)
    return out


def gemm_layers(layers: list, shp: dict) -> list:
    """``(name, K, C_in per group, R, S, in_shape, out_shape, groups)`` of
    every CONV and FC layer, in order — what the op counts and the weight
    generator walk."""
    out = []
    for l in layers:
        if l["type"] not in ("conv", "fc"):
            continue
        cin = shp[l["inputs"][0]]
        if l["type"] == "conv":
            g = l.get("groups", 1)
            out.append((l["name"], l["out"], cin[0] // g, l["k"], l["k"],
                        cin, shp[l["name"]], g))
        else:
            out.append((l["name"], l["out"], int(np.prod(cin)), 1, 1, cin,
                        shp[l["name"]], 1))
    return out


def make_weights(layers: list, input_shape, seed: int) -> dict:
    """He-normal float32 weights and N(0, 0.05) biases, drawn layer by layer
    in order from one ``default_rng(seed)`` stream: conv (K, C/g, R, S),
    fc (K, C*H*W)."""
    rng = np.random.default_rng(seed)
    params = {}
    for name, k, cin_g, r, s, _, _, _ in gemm_layers(
            layers, shapes(layers, input_shape)):
        conv = next(l for l in layers if l["name"] == name)["type"] == "conv"
        w = rng.normal(0, np.sqrt(2.0 / (cin_g * r * s)),
                       (k, cin_g, r, s) if conv else (k, cin_g))
        params[name] = {"w": w.astype(np.float32),
                        "b": rng.normal(0, 0.05, (k,)).astype(np.float32)}
    return params


# ---------------------------------------------------------------------------
# NVDLA fixed-point arithmetic
# ---------------------------------------------------------------------------
def fixed_point(mult: float, max_acc: int) -> tuple:
    """``(m, pre, post)`` with ``x * mult ~ ((x >> pre) * m) >> post``: ``pre``
    keeps ``x >> pre`` within 15 bits for ``|x| <= max_acc``; ``post`` is the
    largest shift that keeps ``m`` a 16-bit signed multiplier."""
    if mult <= 0:
        return 0, 0, 0
    pre = max(0, int(max_acc).bit_length() - 15)
    eff = mult * (1 << pre)
    post = 0
    while eff * (1 << (post + 1)) <= M_MAX and post < 30:
        post += 1
    m = int(round(eff * (1 << post)))
    if m > M_MAX:
        m >>= 1
        post -= 1
    return m, pre, max(post, 0)


def _rha(x: np.ndarray, k) -> np.ndarray:
    """Arithmetic right shift by ``k`` rounding half away from zero, on
    integers held exactly in float64 (every value here is below 2**53, so
    adding, scaling by a power of two and flooring are all exact)."""
    k = np.asarray(k, np.float64)
    a = np.abs(x)
    a += np.where(k > 0, np.exp2(k - 1), 0.0)
    a *= np.exp2(-k)
    np.floor(a, out=a)
    return np.copysign(a, x)


def _requant(acc: np.ndarray, m, pre, post) -> np.ndarray:
    """SDP requantisation of integer ``acc``: ``((acc >> pre) * m) >> post``
    with round-half-away shifts; returns int64."""
    t = _rha(np.asarray(acc, np.float64), pre)
    t *= np.asarray(m, np.float64)
    return _rha(t, post).astype(np.int64)


# ---------------------------------------------------------------------------
# Forward pass
# ---------------------------------------------------------------------------
def _windows(x: np.ndarray, k: int, stride: int, pad: int, fill):
    """(N, C, H, W) -> list of the k*k strided views of the padded input,
    each (N, C, P, Q), in (r, s) order."""
    n, c, h, w = x.shape
    xp = np.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)),
                constant_values=fill) if pad else x
    p, q = (h + 2 * pad - k) // stride + 1, (w + 2 * pad - k) // stride + 1
    return [xp[:, :, r:r + stride * p:stride, s:s + stride * q:stride]
            for r in range(k) for s in range(k)], p, q


def _conv_gemm(x: np.ndarray, w2: np.ndarray, k: int, stride: int, pad: int,
               dtype, groups: int = 1) -> np.ndarray:
    """``w2 (K, C/g*k*k) @ im2col(x)`` per image, in ``dtype`` ->
    (N, K, P*Q).  With ``groups`` g, one GEMM per group: group j's
    ``(K/g, C/g*k*k)`` weights against the windows of its own C/g input
    channels, giving its K/g output channels."""
    n, c = x.shape[:2]
    if k == 1 and pad == 0:
        cols = x[:, :, ::stride, ::stride].astype(dtype)
        cols = cols.reshape(n, c, -1)
    else:
        wins, p, q = _windows(x, k, stride, pad, 0)
        cols = np.empty((n, c, k * k, p * q), dtype)
        for i, v in enumerate(wins):
            cols[:, :, i] = v.reshape(n, c, -1)
        cols = cols.reshape(n, c * k * k, p * q)
    if groups == 1:
        return np.matmul(w2.astype(dtype), cols)
    kk = w2.shape[0]
    cols = cols.reshape(n, groups, -1, cols.shape[-1])
    wg = w2.astype(dtype).reshape(groups, kk // groups, -1)
    return np.matmul(wg, cols).reshape(n, kk, -1)


def quantize_weights(w: np.ndarray, qmax: int, qmin: int) -> tuple:
    """Symmetric per-output-channel: ``(w_q int64, scales float32)``."""
    kk = w.shape[0]
    amax = np.maximum(np.abs(w.reshape(kk, -1)).max(axis=1), 1e-8)
    scales = (amax / qmax).astype(np.float32)
    q = np.clip(np.round(w / scales.reshape((kk,) + (1,) * (w.ndim - 1))),
                qmin, qmax)
    return q.astype(np.int64), scales


def forward(arch_layers: list, input_shape, params: dict, act_scales: dict,
            x: np.ndarray, precision: str = "int8") -> np.ndarray:
    """Logits (N, classes) as float64 for images ``x`` (N, C, H, W) float32.

    Integer precisions return the dequantised logits (int value times the
    output scale); ``bf16`` returns the bf16 logits widened to float."""
    if precision in INT_RANGE:
        return _forward_int(arch_layers, input_shape, params, act_scales, x,
                            precision)
    if precision in ("bf16", "f32"):
        return _forward_float(arch_layers, input_shape, params, x,
                              _bf16 if precision == "bf16" else _f32)[0]
    raise ValueError(f"unknown precision {precision!r}")


def logits(arch: dict, input_shape, seed: int, act_scales: dict,
           x: np.ndarray, precision: str) -> np.ndarray:
    """``forward`` of the configuration's network with the weights of
    ``seed``: everything from the configuration, nothing from the program."""
    layers = build(arch)
    return forward(layers, input_shape, make_weights(layers, input_shape,
                                                     seed),
                   act_scales, x, precision)


def _forward_int(layers, input_shape, params, act_scales, x, precision):
    qmin, qmax = INT_RANGE[precision]
    widen = 127.0 / qmax                     # int4 covers int8's range
    sc = {k: float(v) * widen for k, v in act_scales.items()}
    shp = shapes(layers, input_shape)
    vals = {}
    for l in layers:
        name, t = l["name"], l["type"]
        if t == "input":
            vals[name] = np.clip(np.round(x / np.float32(sc[name])), qmin,
                                 qmax).astype(np.int64)
            continue
        if t == "concat":
            # pure addressing on the engine: every member must already hold
            # the concat's scale (the program's calibration unifies them)
            off = [i for i in l["inputs"] if act_scales[i] != act_scales[name]]
            if off:
                raise ValueError(f"concat {name!r}: members {off} have "
                                 f"scales other than the concat's")
            vals[name] = np.concatenate([vals[i] for i in l["inputs"]], 1)
            continue
        src = vals[l["inputs"][0]]
        s_in, s_out = sc[l["inputs"][0]], sc[name]
        if t in ("conv", "fc"):
            p = params[name]
            wq, wsc = quantize_weights(p["w"], qmax, qmin)
            kk = wq.shape[0]
            k = l["k"] if t == "conv" else 1
            cin_g = wq.reshape(kk, -1).shape[1] // (k * k)
            max_acc = cin_g * k * k * (-qmin) * qmax + 2 ** 20
            acc_scales = np.float32(s_in) * wsc          # float32, per channel
            bias = np.clip(np.round(p["b"] / acc_scales).astype(np.int64),
                           -2 ** 31, 2 ** 31 - 1)
            words = np.array([fixed_point(float(a) / s_out, max_acc)
                              for a in acc_scales], np.int64)
            if t == "conv":
                acc = _conv_gemm(src, wq.reshape(kk, -1), k, l["stride"],
                                 l["pad"], np.float64, l.get("groups", 1))
                oh, ow = shp[name][1:]
            else:
                acc = np.matmul(wq.astype(np.float64),
                                src.reshape(src.shape[0], -1, 1)
                                .astype(np.float64))
                oh = ow = 1
            acc += bias[None, :, None]
            y = _requant(acc, *(words[:, i][None, :, None] for i in range(3)))
            if l.get("relu"):
                y = np.maximum(y, 0)
            vals[name] = np.clip(y, qmin, qmax).reshape(
                src.shape[0], kk, oh, ow)
        elif t == "pool" and l["mode"] == "max":
            wins, _, _ = _windows(src, l["k"], l["stride"], l["pad"], qmin)
            vals[name] = np.maximum.reduce(wins)
        elif t == "pool":
            if l["mode"] == "gap":
                r, s = src.shape[2:]
                acc = src.sum(axis=(2, 3), keepdims=True)
            else:
                r = s = l["k"]
                wins, _, _ = _windows(src, r, l["stride"], l["pad"], 0)
                acc = np.sum(wins, axis=0)
            m, pre, post = fixed_point(s_in / (s_out * r * s), r * s * 128)
            vals[name] = np.clip(_requant(acc, m, pre, post), qmin, qmax)
        elif t == "add":
            a_name, b_name = l["inputs"]
            y = sum(_requant(vals[i], *fixed_point(sc[i] / s_out, 128))
                    for i in (a_name, b_name))
            if l.get("relu"):
                y = np.maximum(y, 0)
            vals[name] = np.clip(y, qmin, qmax)
        else:
            raise ValueError(t)
    out = layers[-1]["name"]
    return vals[out].reshape(x.shape[0], -1).astype(np.float64) * sc[out]


def _bf16(a: np.ndarray) -> np.ndarray:
    """Round to bfloat16 (nearest even), held as float32."""
    import ml_dtypes
    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def _f32(a: np.ndarray) -> np.ndarray:
    return np.asarray(a, np.float32)


def _forward_float(layers, input_shape, params, x, rnd):
    """(logits, every layer's output) with ``rnd`` applied to each stored
    tensor: ``_bf16`` for nv_full, ``_f32`` for the float network."""
    shp = shapes(layers, input_shape)
    vals = {}
    for l in layers:
        name, t = l["name"], l["type"]
        if t == "input":
            vals[name] = rnd(x)
            continue
        if t == "concat":
            vals[name] = np.concatenate([vals[i] for i in l["inputs"]], 1)
            continue
        src = vals[l["inputs"][0]]
        if t in ("conv", "fc"):
            p = params[name]
            kk = p["w"].shape[0]
            w2 = rnd(p["w"].reshape(kk, -1))
            if t == "conv":
                acc = _conv_gemm(src, w2, l["k"], l["stride"], l["pad"],
                                 np.float32, l.get("groups", 1))
                oh, ow = shp[name][1:]
            else:
                acc = np.matmul(w2, src.reshape(src.shape[0], -1, 1))
                oh = ow = 1
            acc = acc + p["b"].astype(np.float32)[None, :, None]
            if l.get("relu"):
                acc = np.maximum(acc, 0)
            vals[name] = rnd(acc).reshape(src.shape[0], kk, oh, ow)
        elif t == "pool" and l["mode"] == "max":
            wins, _, _ = _windows(src, l["k"], l["stride"], l["pad"],
                                  -np.inf)
            vals[name] = np.maximum.reduce(wins)
        elif t == "pool":
            if l["mode"] == "gap":
                acc = src.sum(axis=(2, 3), keepdims=True, dtype=np.float32)
                area = src.shape[2] * src.shape[3]
            else:
                wins, _, _ = _windows(src, l["k"], l["stride"], l["pad"], 0)
                acc = np.sum(wins, axis=0, dtype=np.float32)
                area = l["k"] * l["k"]
            vals[name] = rnd(acc / np.float32(area))
        elif t == "add":
            y = vals[l["inputs"][0]] + vals[l["inputs"][1]]
            if l.get("relu"):
                y = np.maximum(y, 0)
            vals[name] = rnd(y)
        else:
            raise ValueError(t)
    return vals[layers[-1]["name"]].reshape(x.shape[0], -1).astype(
        np.float64), vals


def calibrate(layers: list, input_shape, params: dict, images: np.ndarray,
              percentile: float = 99.99) -> dict:
    """Activation scales ``amax / 127`` per layer, ``amax`` the largest over
    ``images`` of the ``percentile`` of |activation| in the float network,
    then unified as the program does, in one pass in layer order: a max pool
    keeps its input's scale (it has no requantiser), and the members of a
    concat take the concat's (it is pure addressing)."""
    amax = {l["name"]: 1e-8 for l in layers}
    for x in images:
        _, vals = _forward_float(layers, input_shape, params, x[None], _f32)
        for name, a in vals.items():
            amax[name] = max(amax[name],
                             float(np.percentile(np.abs(a), percentile)))
    scales = {k: v / 127 for k, v in amax.items()}
    for l in layers:
        if l["type"] == "pool" and l["mode"] == "max":
            scales[l["name"]] = scales[l["inputs"][0]]
        if l["type"] == "concat":
            for i in l["inputs"]:
                scales[i] = scales[l["name"]]
    return scales
