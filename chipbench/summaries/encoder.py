"""The plain reference of the encoder summary (``"summary": "encoder"``),
and the numbers that compare a run's summaries with it.

A summary is the paper's (section 4.1): a stratified k-sample coreset of
the client's data, the encoder's features of those samples, their mean per
label, then P(y): ``[C * H + C]``.  Written from the semantics the
configuration states, in numpy and plain ``jax.numpy``; nothing here
imports the program or reads what it made.

The encoder (paper: a MobileNet hidden layer) is a stem convolution, two
separable blocks and a linear head, widths 16/32/64, each convolution
followed by a channel RMS norm and relu6, with a global average pool
before the head; its weights are drawn as normal / sqrt(fan_in) from PRNG
key 7 over the leaves in sorted order, norm scales 1.

Precision.  The configuration states float32 arrays, with convolutions
and matmuls at the TPU's default precision for float32: one bfloat16 pass,
operands rounded to bfloat16 and products accumulated in float32 (on a
CPU, where the tests run, the default is float32).  The
per-label mean is an exact float32 mean.  The control takes one step below
each: bfloat16 arrays, and operands rounded to float8 (e4m3).
"""
from __future__ import annotations

import functools
import json
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

ENCODER_KEY = 7
ENCODER_WIDTHS = (16, 32, 64)
# operands of every convolution and matmul: float32 at HIGHEST, the
# stated precision (the device's default for float32: one bfloat16 pass
# with float32 accumulation on a TPU, float32 on a CPU), and the
# control's, one step below one bfloat16 pass
EXACT = "float32"
STATED = "bfloat16" if jax.default_backend() == "tpu" else EXACT
CONTROL = "float8_e4m3fn"
SAMPLE = 48              # window summaries compared per run


# ---------------------------------------------------------------------------
# the encoder


def encoder_params(in_channels: int, feature_dim: int) -> dict:
    """The encoder's weights, by the rule the module docstring states."""
    w = ENCODER_WIDTHS
    shapes = {"stem": (3, 3, in_channels, w[0]), "stem_norm": None}
    for i in range(len(w) - 1):
        shapes[f"block_{i}"] = {"dw": (3, 3, 1, w[i]), "dw_norm": None,
                                "pw": (1, 1, w[i], w[i + 1]),
                                "pw_norm": None}
    shapes["head"] = (w[-1], feature_dim)
    norm_width = {"stem_norm": w[0]}
    for i in range(len(w) - 1):
        norm_width[f"block_{i}/dw_norm"] = w[i]
        norm_width[f"block_{i}/pw_norm"] = w[i + 1]
    leaves = []

    def walk(tree, prefix):
        for k in sorted(tree):
            path = f"{prefix}{k}"
            if isinstance(tree[k], dict):
                walk(tree[k], path + "/")
            else:
                leaves.append((path, tree[k]))
    walk(shapes, "")
    keys = jax.random.split(jax.random.PRNGKey(ENCODER_KEY), len(leaves))
    out = {}
    for (path, shape), key in zip(leaves, keys):
        if shape is None:
            out[path] = np.ones(norm_width[path], np.float32)
        else:
            fan_in = math.prod(shape[:-1])
            out[path] = np.asarray(jax.random.normal(key, shape, jnp.float32)
                                   * jnp.float32(1.0 / math.sqrt(fan_in)))
    return out


def _encode(params: dict, images, dtype, operand):
    """images [B, H, W, C] -> [B, feature_dim], every array in ``dtype``,
    every convolution and matmul taking its operands rounded to
    ``operand`` and accumulating in float32 (``float32`` operands run at
    ``HIGHEST``)."""
    exact = jnp.dtype(operand) == jnp.float32
    prec = jax.lax.Precision.HIGHEST if exact else None

    def operands(*xs):
        if exact:
            return [x.astype(dtype) for x in xs]
        # rounded to the operand precision, then handed over as bfloat16,
        # which holds bfloat16 and float8 values exactly
        return [x.astype(operand).astype(jnp.bfloat16) for x in xs]

    def conv(x, w, stride, groups=1):
        x, w = operands(x, w)
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups, precision=prec,
            preferred_element_type=dtype)

    def norm(x, scale):
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return (x * jax.lax.rsqrt(var + 1e-6) * scale.astype(dtype)).astype(dtype)

    x = images.astype(dtype)
    x = jax.nn.relu6(norm(conv(x, params["stem"], 2), params["stem_norm"]))
    i = 0
    while f"block_{i}/dw" in params:
        p = f"block_{i}/"
        cin = params[p + "dw"].shape[-1]
        x = jax.nn.relu6(norm(conv(x, params[p + "dw"], 1, cin),
                              params[p + "dw_norm"]))
        x = jax.nn.relu6(norm(conv(x, params[p + "pw"], 2),
                              params[p + "pw_norm"]))
        i += 1
    x = jnp.mean(x, axis=(1, 2)).astype(dtype)
    x, w = operands(x, params["head"])
    return jnp.dot(x, w, precision=prec, preferred_element_type=dtype)


_encode_jit = jax.jit(_encode, static_argnames=("dtype", "operand"))


def encode(params: dict, images, dtype=jnp.float32, operand=STATED):
    """Features of ``images``: arrays in ``dtype``, convolution and matmul
    operands rounded to ``operand``."""
    return _encode_jit(params, jnp.asarray(images), dtype=jnp.dtype(dtype),
                       operand=jnp.dtype(operand).name)


# ---------------------------------------------------------------------------
# the coreset


def _pow2(n: int, base: int = 8) -> int:
    b = base
    while b < n:
        b *= 2
    return b


@functools.partial(jax.jit, static_argnames=("num_classes", "k"))
def _quotas(labels, valid, num_classes: int, k: int):
    """Largest-remainder quotas of k over the classes' counts, capped at
    each count, ties to the lower class id.  Float32 on the device, as the
    configurations state: a float32 quotient on the TPU is not always the
    correctly rounded one, and a quota that lands on a whole number can
    round either way."""
    counts = jnp.zeros(num_classes, jnp.int32).at[labels].add(
        valid.astype(jnp.int32))
    exact = k * counts / jnp.maximum(jnp.sum(counts), 1)
    base = jnp.minimum(jnp.floor(exact).astype(jnp.int32), counts)
    rem = jnp.where(counts > base, exact - base, -1.0)
    order = jnp.argsort(-rem, stable=True)
    bump = jnp.zeros(num_classes, jnp.int32).at[order].set(
        (jnp.arange(num_classes) < k - jnp.sum(base)).astype(jnp.int32))
    return jnp.minimum(base + jnp.where(counts > base, bump, 0), counts)


def coreset(labels: np.ndarray, num_classes: int, k: int, key,
            exact_quotas: bool = False):
    """Stratified k-sample coreset of one client's data padded to a power
    of two (at least 8) rows: per-class quotas by largest remainder (ties
    to the lower class id), then the samples of highest uniform priority
    within each class.  The quotas are float32 on the device, or with
    ``exact_quotas`` in integers.  Returns (indices, kept mask), both of
    length min(k, padded rows)."""
    n_valid = labels.shape[0]
    n = _pow2(n_valid)
    lab = np.zeros(n, np.int64)
    lab[:n_valid] = labels
    valid = np.arange(n) < n_valid
    if exact_quotas:
        counts = np.bincount(labels, minlength=num_classes)
        base = (k * counts) // max(n_valid, 1)
        rem = np.where(counts > base, (k * counts) % max(n_valid, 1), -1)
        bump = np.zeros(num_classes, np.int64)
        bump[np.argsort(-rem, kind="stable")] = (
            np.arange(num_classes) < k - base.sum())
        quotas = np.minimum(base + np.where(counts > base, bump, 0), counts)
    else:
        quotas = np.asarray(_quotas(jnp.asarray(lab, jnp.int32),
                                    jnp.asarray(valid), num_classes, k),
                            np.int64)
    pri = np.asarray(jax.random.uniform(key, (n,)))
    pri = np.where(valid, pri, -1.0)
    pri_rank = np.argsort(np.argsort(-pri, kind="stable"), kind="stable")
    sort_key = np.where(valid, lab * (n + 1) + pri_rank,
                        num_classes * (n + 1) + pri_rank)
    order = np.argsort(sort_key, kind="stable")
    s_lab, s_valid = lab[order], valid[order]
    starts = np.concatenate([[0], np.cumsum(
        np.bincount(s_lab[s_valid], minlength=num_classes))[:-1]])
    rank = np.arange(n) - starts[s_lab]
    keep = s_valid & (rank < quotas[s_lab])
    comp = np.argsort(~keep, kind="stable")
    idx = order[comp][:k]
    kept = keep[comp][:k]
    return np.where(kept, idx, 0), kept


def summary(params: dict, images: np.ndarray, labels: np.ndarray,
            num_classes: int, k: int, key, dtype=jnp.float32,
            operand=STATED, exact_quotas: bool = False) -> np.ndarray:
    """One client's summary, [C * H + C]: arrays in ``dtype``, encoder
    operands rounded to ``operand``."""
    idx, kept = coreset(labels, num_classes, k, key, exact_quotas)
    feats = np.asarray(jnp.asarray(encode(params, images[idx], dtype,
                                          operand), jnp.float32), np.float64)
    h = feats.shape[1]
    sums = np.zeros((num_classes, h), np.float64)
    np.add.at(sums, labels[idx][kept], feats[kept])
    cnt = np.bincount(labels[idx][kept], minlength=num_classes)
    means = sums / np.maximum(cnt, 1)[:, None]
    py = np.bincount(labels, minlength=num_classes) / max(labels.shape[0], 1)
    out = np.concatenate([means.ravel(), py])
    return np.asarray(jnp.asarray(out, dtype).astype(jnp.float32))


# ---------------------------------------------------------------------------
# the comparison


def compare(cfg: dict, items: list, control: bool = False) -> dict:
    """{name: (value, limit)} over ``items``, a list of ``(round, client,
    images, labels, summary)``, the summary being what the run produced
    (ignored with ``control``: the reference one precision step below the
    stated takes its place).

    * ``py_gap``: the largest |P(y) - reference|;
    * ``mean_gap``: the largest, over clients, of the RMS gap of the
      per-label means from the reference at the stated precision, over
      the RMS of the reference's means.  The coreset's quotas are float32
      on the device, or integers, whichever is nearer: a quota on a whole
      number rounds either way in float32, and then a class holds a sample
      more or fewer, which is not a precision question.
    """
    c_num = cfg["num_classes"]
    h, k = cfg["server"]["encoder_dim"], cfg["server"]["coreset_k"]
    params = encoder_params(cfg["feature_shape"][-1], h)
    split = c_num * h
    py_gap = mean_gap = 0.0
    seen = {"max_rel_stated": 0.0, "rms_highest": 0.0}

    def rms(a, b):
        d = a[:split] - b[:split]
        return float(np.sqrt(np.mean(d * d)
                             / max(float(np.mean(b[:split] ** 2)), 1e-30)))

    for rnd, cid, images, labels, got in items:
        key = jax.random.PRNGKey(rnd * 100003 + cid)
        want = [summary(params, images, labels, c_num, k, key,
                        exact_quotas=q) for q in (False, True)]
        if control:
            got = summary(params, images, labels, c_num, k, key,
                          dtype=jnp.bfloat16, operand=CONTROL)
        gaps = [rms(got, w) for w in want]
        near = want[int(np.argmin(gaps))]
        mean_gap = max(mean_gap, min(gaps))
        py_gap = max(py_gap, float(np.max(np.abs(got[split:] - near[split:]))))
        top = max(float(np.max(np.abs(near[:split]))), 1e-30)
        seen["max_rel_stated"] = max(
            seen["max_rel_stated"],
            float(np.max(np.abs(got[:split] - near[:split]))) / top)
        high = summary(params, images, labels, c_num, k, key,
                       operand=EXACT,
                       exact_quotas=bool(np.argmin(gaps)))
        seen["rms_highest"] = max(seen["rms_highest"], rms(got, high))
    print("summary gaps: " + json.dumps(seen), file=sys.stderr, flush=True)
    limits = cfg["check_limits"]
    return {"py_gap": (py_gap, limits["py_gap"]),
            "mean_gap": (mean_gap, limits["mean_gap"])}
