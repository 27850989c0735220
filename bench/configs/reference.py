"""Plain reference of the configurations' conv-stack basecallers, and the
family's surface that ``bench.configs.model_of`` hands the harness.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
each conv layer is a sum over its taps of strided slices times the tap's
weight matrix, ReLU between layers, no ReLU after the head, then greedy
argmax and the CTC collapse in numpy.  The reference imports nothing of
the program; only ``offline_engine``, which builds the program under test,
does.

``operands`` rounds every layer's inputs and weights before the product
(``f32`` leaves them alone), which is how the control of ``correct`` is
computed: the reference at a lower precision than the configuration's.

The conv family's work is counted by ``bench/lib/cost.py``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import cost

BLANK = 0
WEIGHT_KINDS = ("he_normal", "step_levels")
CONTROLS = ("bf16", "fp8", "int4")
# the offline engine's jitted forward (``core.basecaller.apply``)
FORWARD_MODULE = "jit__apply_jit"
CALIBRATION_SAMPLES = 4104
CALIBRATION_STEPS = 40


# ------------------------------------------------------------- weights ----
def make_params(cfg: dict, seed: int):
    """The configuration's weights, made on the device in one call: from
    the configuration's own ``weights.seed`` where it names one (a deployed
    model is one set of weights), else from ``seed``."""
    kind = cfg["weights"]["kind"]
    seed = cfg["weights"].get("seed", seed)
    if kind == "he_normal":
        return _he_normal(tuple(cfg["kernels"]), tuple(cfg["channels"]),
                          tuple(cfg["strides"]), cfg["in_channels"],
                          jax.random.key(seed),
                          calibration_signal(cfg["signal"], seed),
                          math.prod(cfg["strides"])
                          / cfg["signal"]["mean_dwell"])
    if kind == "step_levels":
        return _step_levels(tuple(cfg["weights"]["levels"]))
    raise ValueError(f"unknown weights kind {kind!r}")


def calibration_signal(spec: dict, seed: int) -> np.ndarray:
    """(64, CALIBRATION_SAMPLES) rows of the configuration's own signal
    model over random bases, from the seed."""
    from bench.lib import signals

    rng = np.random.default_rng([seed, 9])
    n_bases = CALIBRATION_SAMPLES // 2
    seqs = [rng.integers(1, 5, size=n_bases) for _ in range(64)]
    sig, off = signals.encode(rng, seqs, spec)
    return np.stack([sig[off[i]:off[i] + CALIBRATION_SAMPLES]
                     for i in range(64)])


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 6))
def _he_normal(kernels, channels, strides, cin, key, x, tokens_per_frame):
    """He-normal weights, then the head's bias set on the calibration
    signal ``x``: minus the mean head logit of each class, moved by a few
    steps against the share of frames it wins, and the blank's moved until
    the greedy calls come at ``tokens_per_frame``.  Without it the bases
    called per frame swing several-fold from one seed to the next, and
    with them the work of every Read-Until tick."""
    params = {}
    c = cin
    for i, (k, cout) in enumerate(zip(kernels, channels)):
        key, sub = jax.random.split(key)
        w = jax.random.normal(sub, (k, c, cout), jnp.float32)
        params[f"conv{i + 1}"] = {"w": w * jnp.sqrt(2.0 / (k * c)),
                                  "b": jnp.zeros((cout,), jnp.float32)}
        c = cout
    z = _logits(params, x, kernels=kernels, strides=strides,
                padding="same", operands="f32")
    b = -jnp.mean(z, axis=(0, 1))
    spread = jnp.std(z)
    for step in range(CALIBRATION_STEPS):
        won = jax.nn.one_hot(jnp.argmax(z + b, axis=-1), z.shape[-1])
        share = jnp.maximum(jnp.mean(won, axis=(0, 1)), 1e-3)
        b = b - 0.3 * 0.9 ** step * spread * jnp.log(share * z.shape[-1])
    # then the blank's bias, by bisection, so that the calls come at one
    # base per base of signal: total stride / mean dwell bases per frame
    lo, hi = -4.0 * spread, 4.0 * spread
    for _ in range(CALIBRATION_STEPS):
        mid = 0.5 * (lo + hi)
        many = _token_rate(z + b.at[BLANK].add(mid)) > tokens_per_frame
        lo, hi = jnp.where(many, mid, lo), jnp.where(many, hi, mid)
    params[f"conv{len(kernels)}"]["b"] = b.at[BLANK].add(0.5 * (lo + hi))
    return params


def _token_rate(logits):
    """Tokens the greedy CTC collapse emits per frame."""
    best = jnp.argmax(logits, axis=-1)
    prev = jnp.pad(best[:, :-1], ((0, 0), (1, 0)))
    return jnp.mean((best != BLANK) & (best != prev))


@functools.partial(jax.jit, static_argnums=(0,))
def _step_levels(levels):
    """Nearest-level scoring of 2-sample segments (score = 2 mu mean(x) -
    mu^2, written as a K=2 conv), then a 1x1 identity head."""
    mu = jnp.asarray(levels, jnp.float32)
    n = len(levels)
    return {"conv1": {"w": jnp.broadcast_to(mu, (2, 1, n)),
                      "b": -(mu ** 2)},
            "conv2": {"w": jnp.eye(n, dtype=jnp.float32)[None],
                      "b": jnp.zeros((n,), jnp.float32)}}


# ------------------------------------------------------------ rounding ----
def _round(x, operands: str):
    if operands == "f32":
        return x
    if operands == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if operands == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if operands == "int4":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 7.0
        return jnp.clip(jnp.round(x / scale), -7, 7) * scale
    raise ValueError(f"unknown operand precision {operands!r}")


# ------------------------------------------------------------- forward ----
def _logits(params, x, *, kernels, strides, padding, operands):
    h = x[..., None]
    n = len(kernels)
    for i, (k, s) in enumerate(zip(kernels, strides)):
        t = h.shape[1]
        if padding == "stream":        # K - stride zero rows on the left
            t_out = t // s
            h = jnp.pad(h, ((0, 0), (k - s, 0), (0, 0)))
        else:                          # centred, T_out = ceil(T / stride)
            t_out = -(-t // s)
            pad = max((t_out - 1) * s + k - t, 0)
            h = jnp.pad(h, ((0, 0), (pad // 2, pad - pad // 2), (0, 0)))
        p = params[f"conv{i + 1}"]
        hq, wq = _round(h, operands), _round(p["w"], operands)
        y = p["b"]
        for tap in range(k):
            y = y + jnp.einsum(
                "btc,cd->btd", hq[:, tap:tap + s * (t_out - 1) + 1:s, :],
                wq[tap], precision=jax.lax.Precision.HIGHEST)
        h = jax.nn.relu(y) if i < n - 1 else y
    return h


@functools.partial(jax.jit, static_argnames=("kernels", "strides",
                                             "padding", "operands"))
def _forward(params, x, *, kernels, strides, padding, operands):
    return jnp.argmax(_logits(params, x, kernels=kernels, strides=strides,
                              padding=padding, operands=operands),
                      axis=-1).astype(jnp.int32)


def frame_classes(params, cfg: dict, x: np.ndarray, *, padding: str,
                  operands: str = "f32", block: int = 256) -> np.ndarray:
    """Greedy class per output frame of every row of ``x`` (rows, T),
    computed in blocks of rows so that it fits next to nothing else."""
    out = []
    for i in range(0, len(x), block):
        xb = np.zeros((block, x.shape[1]), np.float32)
        part = x[i:i + block]
        xb[:len(part)] = part
        out.append(np.asarray(_forward(
            params, jnp.asarray(xb), kernels=tuple(cfg["kernels"]),
            strides=tuple(cfg["strides"]), padding=padding,
            operands=operands))[:len(part)])
    return np.concatenate(out) if out else np.zeros((0, 0), np.int32)


def collapse(classes: np.ndarray, valid: int) -> tuple[np.ndarray, np.ndarray]:
    """CTC collapse of one row's first ``valid`` frame classes (BLANK before
    the first frame).  Returns (tokens, frame index of each token)."""
    best = classes[:valid]
    prev = np.concatenate([[BLANK], best[:-1]])
    keep = (best != BLANK) & (best != prev)
    return best[keep].astype(np.int32), np.nonzero(keep)[0]


# ------------------------------------------------- the family's surface ----
def n_params(cfg: dict) -> int:
    return cost.n_params(cfg)


def macs_per_sample(cfg: dict) -> float:
    return cost.macs_per_sample(cfg)


def forward_work(cfg: dict, rows: int, samples: int) -> dict:
    return cost.forward(cfg, rows, samples)


def offline_engine(params, cfg: dict, *, batch: int, chunk: int, seed: int):
    """The program's batch basecall engine for ``params``, from its normal
    entry."""
    from repro.core import basecaller as bc
    from repro.engine import build

    bc_cfg = bc.BasecallerConfig(kernels=tuple(cfg["kernels"]),
                                 channels=tuple(cfg["channels"]),
                                 strides=tuple(cfg["strides"]),
                                 in_channels=cfg["in_channels"])
    return build("basecall", params=params, cfg=bc_cfg, batch=batch,
                 chunk=chunk, seed=seed)


def offline_reads(params, cfg: dict, x: np.ndarray, *,
                  operands: str = "f32") -> list[np.ndarray]:
    """The reference's tokens of each whole row of ``x`` (centred padding),
    at ``highest`` precision and in blocks of rows."""
    with jax.default_matmul_precision("highest"):
        classes = frame_classes(params, cfg, x, padding="same",
                                operands=operands)
    return [collapse(c, len(c))[0] for c in classes]
