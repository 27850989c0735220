"""Fused persistent streaming step: conv stack → CTC collapse → counters
in ONE lane-major Pallas program.

The paper's SoC keeps the basecall hot loop resident in on-chip memory —
activations never bounce through DRAM between accelerator dispatches.  The
unfused flowcell tick is already one jitted fn, but *inside* it each conv
layer, the k=1 GEMM head, the CTC greedy collapse, and the per-lane policy
counters are separate fabric dispatches with HBM round-trips between them.
This module collapses that chain flash-decoding style:

  * **grid = lane blocks.**  One program instance owns ``block_l`` channel
    lanes; everything those lanes need for the whole chunk — conv carries,
    intermediate activations, the CTC ``prev_class`` carry, the per-lane
    ``bases``/``ticks`` counters — stays resident in VMEM across
    conv1..N → head GEMM → incremental CTC collapse → counter epilogue.
    Only tokens, lengths, counters and the next-chunk carries are written
    back, once per tick.
  * **lane-reset folding.**  A ``reset`` mask rides into the kernel; stale
    state of freshly recycled lanes (carries, ``prev_class`` → BLANK,
    counters → 0) is zeroed *inside* the program, replacing the host-side
    reset scatter the unfused tick performs — bitwise-equal by construction
    (zeroing then computing == computing on zeroed inputs).
  * **native int8.**  Layers whose weights are stored
    :class:`repro.quant.QuantizedTensor` (calibrated static activation
    scales) MAC int8→int32 in-kernel and dequantize with the exact
    ``ops._int8_epilogue`` arithmetic; counted under
    ``fabric.precision.fused_stream.int8``.  Integer GEMMs have one answer,
    so fused int8 == unfused int8 bitwise.

Registered as the fabric op ``"fused_stream"`` with the usual three
targets.  The **reference target literally composes the unfused pieces**
(`ops._conv1d_reference` / `ops._matmul_reference` per layer — the same
functions ``ops.conv1d_stream`` / ``ops.mat_mul`` dispatch to — then
``ctc.greedy_decode_stream`` and the counter update), so reference parity
is definitional, and the whole chain is wrapped in
``fabric.batched_counts()`` so it reports **one** counter-flush event per
tick instead of one host callback per inner op.

Fallback taxonomy (counted ``fabric.fallback.fused_stream.<reason>``):

  ``lanes_lt_8``       fewer than 8 lanes reach the op (per *shard* under a
                       lane mesh — sharding can suppress the kernel)
  ``dtype``            basecaller configured for a non-float32 dtype
  ``int8_dynamic_act`` quantized weights without calibrated act scales (the
                       dynamic absmax is a cross-lane reduction a
                       lane-blocked program cannot take)
  ``precision_policy`` a tuned ``precision="int8"`` bucket on float weights
                       (per-call weight requant stays on the unfused path)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import ctc
from repro.kernels import fabric
from repro.kernels import fabric as _fabric_mod
from repro.kernels import ops as _ops
from repro.kernels.fabric import pow2_bucket as _pb
from repro.kernels.matmul import _ACTIVATIONS
from repro.quant import core as qcore
from repro.utils.shapes import next_multiple

QMAX = qcore.QMAX


def _specs(cfg):
    from repro.core import basecaller as bc
    return bc.stream_layer_specs(cfg)


def _layer_precisions(cfg, lanes: int, chunk: int, policy) -> tuple:
    """The per-layer precision policy the *unfused* step would resolve.

    The unfused path consults the conv1d/matmul tuning buckets per layer; a
    bucket that pins ``precision="int8"`` must behave identically when the
    layer runs inside the fused program, so the fused wrapper resolves the
    same buckets up front and threads the answers through dispatch (static
    tuple — part of the trace signature)."""
    out = []
    t = chunk
    for sp in _specs(cfg):
        if sp.is_head:
            args = (_fabric_mod.ShapeProxy((lanes * t, sp.cin)),
                    _fabric_mod.ShapeProxy((sp.cin, sp.cout)))
            tune = _fabric_mod.resolved_tuning("matmul", args, {}, policy)
        else:
            args = (_fabric_mod.ShapeProxy((lanes, t + sp.carry_rows,
                                            sp.cin)),
                    _fabric_mod.ShapeProxy((sp.ksize, sp.cin, sp.cout)))
            tune = _fabric_mod.resolved_tuning("conv1d", args, {}, policy)
        out.append(tune.get("precision", "auto"))
        t //= sp.stride
    return tuple(out)


# ========================================================= public wrapper ==
def fused_stream_step(params, lane_state, rows, frame_pads, reset=None, *,
                      cfg, fabric=None, block_l=None):
    """One fused flowcell tick over all lanes.

    ``lane_state`` is the runtime's lane-major pytree (``conv`` carries,
    ``prev_class``, ``bases``, ``ticks``); ``rows`` (lanes, chunk) raw
    signal; ``frame_pads`` (lanes, n_frames) 1.0 where a frame is padding;
    ``reset`` (lanes,) nonzero where the lane starts a new read this tick
    (its stale state is zeroed inside the op).  Returns
    ``(tokens, lens, new_lane_state)`` — the exact contract of the unfused
    ``build_step_fn`` step after ``_reset_lanes``.
    """
    pol = _fabric_mod.as_policy(fabric)
    lanes, chunk = rows.shape
    if chunk % cfg.total_stride:
        raise ValueError(f"chunk length {chunk} must be a multiple of "
                         f"total_stride={cfg.total_stride}")
    if reset is None:
        reset = jnp.zeros((lanes,), jnp.float32)
    precisions = _layer_precisions(cfg, lanes, chunk, pol)
    with _fabric_mod.batched_counts():
        return _fabric_mod.dispatch(
            "fused_stream", rows, frame_pads, reset,
            lane_state["prev_class"], lane_state["bases"],
            lane_state["ticks"], tuple(lane_state["conv"]), params,
            cfg=cfg, precisions=precisions, fabric=pol,
            tune={"block_l": block_l})


# ======================================================= reference target ==
def _fused_reference(rows, pads, reset, prev, bases, ticks, conv, params, *,
                     cfg, precisions, tune=None):
    """Composition of the unfused pieces — parity is definitional.

    Calls the exact per-layer reference functions ``conv1d_stream`` /
    ``mat_mul`` dispatch to (with the same resolved precision policy), then
    ``ctc.greedy_decode_stream`` and the counter update, with the lane
    reset folded in up front."""
    del tune
    specs = _specs(cfg)
    rmask = reset > 0
    x = rows.astype(cfg.dtype)[..., None]
    if any(qcore.is_quantized(params[sp.name]["w"]) for sp in specs):
        fabric.record("fabric.precision.fused_stream.int8")
    new_conv = []
    for i, sp in enumerate(specs):
        p = params[sp.name]
        if sp.is_head:
            w = p["w"]
            if qcore.is_quantized(w):
                w2 = qcore.QuantizedTensor(
                    q=w.q[0], scale=w.scale,
                    axis=None if w.axis is None else 1,
                    act_scale=w.act_scale)
            else:
                w2 = w[0]
            bsz, t, cin = x.shape
            y = _ops._matmul_reference(
                x.reshape(bsz * t, cin), w2, p["b"],
                activation=sp.activation, tune={"precision": precisions[i]})
            x = y.reshape(bsz, t, sp.cout)
            new_conv.append(conv[i])
        else:
            carry = conv[i]
            if sp.carry_rows:
                carry = jnp.where(rmask[:, None, None],
                                  jnp.zeros((), carry.dtype), carry)
            buf = jnp.concatenate([carry.astype(x.dtype), x], axis=1)
            x = _ops._conv1d_reference(
                buf, p["w"], p["b"], stride=sp.stride,
                activation=sp.activation, tune={"precision": precisions[i]})
            new_conv.append(buf[:, buf.shape[1] - sp.carry_rows:, :])
    prev0 = jnp.where(rmask, ctc.BLANK, prev)
    tokens, lens, new_prev = ctc.greedy_decode_stream(x, prev0, pads)
    new_lane = {
        "conv": new_conv,
        "prev_class": new_prev,
        "bases": jnp.where(rmask, 0, bases) + lens.astype(jnp.int32),
        "ticks": jnp.where(rmask, 0, ticks) + 1,
    }
    return tokens, lens, new_lane


# ========================================================== pallas target ==
def _quantize_tap(tap, sa):
    # static-act-scale quantization, exactly qcore.quantize: the same
    # round/clip the unfused int8 path applies per layer (elementwise, so
    # quantizing a strided tap == striding the quantized buffer)
    return jnp.clip(jnp.round(tap / sa), -QMAX, QMAX).astype(jnp.int8)


def _fused_kernel(refs, *, meta, block_l, chunk, n_frames):
    """The persistent program body for one block of lanes.

    ``refs`` is the flat (inputs..., outputs..., scratch...) ref list;
    ``meta`` is the static per-layer plan built by :func:`_fused_pallas`.

    Layout.  Hidden layers run lane-batched with time on sublanes and
    channels on lanes, ``(block_l, T, C)``; each layer's ``[carry, x]``
    input is staged in a VMEM scratch so every conv tap is a strided *ref*
    load (Mosaic lowers no strided value slice).  The last layer runs per
    lane *transposed*, ``(C, F)`` with frames on lanes, so the per-frame
    argmax, CTC collapse and token rows need no sublane<->lane relayout; the
    prefix sum and the one-frame shift of the collapse are exact 0/1 GEMMs.
    """
    it = iter(refs)
    rows_ref = next(it)
    pads_ref = next(it)
    reset_ref = next(it)
    reset3_ref = next(it)
    prev_ref = next(it)
    bases_ref = next(it)
    ticks_ref = next(it)
    carry_in, w_refs = {}, {}
    for m in meta:
        if m["carry_rows"]:
            carry_in[m["i"]] = next(it)
        w_refs[m["i"]] = tuple(next(it) for _ in
                               range(4 if m["quantized"] else 2))
    tokens_ref = next(it)
    lens_ref = next(it)
    prev_out_ref = next(it)
    bases_out_ref = next(it)
    ticks_out_ref = next(it)
    carry_out = {m["i"]: next(it) for m in meta if m["carry_rows"]}
    stage = {m["i"]: next(it) for m in meta if m["staged"]}
    best_ref = next(it)

    rmask = reset_ref[...] > 0.0                       # (bl, 1)
    x = rows_ref[...].astype(jnp.float32)              # (bl, T, 1)
    last = meta[-1]
    for m in meta:
        i, ksize, stride = m["i"], m["ksize"], m["stride"]
        t_in = x.shape[1]
        t_out = t_in // stride
        cr = m["carry_rows"]
        if m["staged"]:
            scr = stage[i]                             # (bl, cr + T, cin)
            if cr:
                carry = jnp.where(reset3_ref[...] > 0.0, 0.0,
                                  carry_in[i][...])
                scr[:, :cr, :] = carry
            scr[:, cr:, :] = x
            if cr:
                carry_out[i][...] = scr[:, t_in:, :]
        if m is last:
            break
        cin = x.shape[2]
        if m["quantized"]:
            wq_ref, scale_ref, bias_ref, sa_ref = w_refs[i]
            sa = sa_ref[0, 0]
        else:
            w_ref, bias_ref = w_refs[i]
        acc = None
        for k in range(ksize):
            tap = (scr[:, pl.ds(k, t_out, stride=stride), :] if m["staged"]
                   else x).reshape(block_l * t_out, cin)
            if m["quantized"]:
                part = jnp.dot(_quantize_tap(tap, sa), wq_ref[k],
                               preferred_element_type=jnp.int32)
            else:
                part = jnp.dot(tap, w_ref[k],
                               preferred_element_type=jnp.float32)
            acc = part if acc is None else acc + part
        if m["quantized"]:
            # ops._int8_epilogue arithmetic, term for term
            out = acc.astype(jnp.float32) * scale_ref[...]
        else:
            out = acc
        out = out + bias_ref[...].astype(out.dtype)
        x = _ACTIVATIONS[m["activation"]](out).astype(jnp.float32)
        x = x.reshape(block_l, t_out, m["cout"])

    # -------- last layer, per lane and transposed: logits (C, F) ---------
    i, ksize, stride = last["i"], last["ksize"], last["stride"]
    if last["quantized"]:
        wq_ref, scale_ref, bias_ref, sa_ref = w_refs[i]  # (K, C, cin) ...
        sa = sa_ref[0, 0]
    else:
        w_ref, bias_ref = w_refs[i]                      # (K, C, cin), (C, 1)
    nt = (((1,), (1,)), ((), ()))                        # (C,cin)x(F,cin)^T
    for lane in range(block_l):
        acc = None
        for k in range(ksize):
            tap = (stage[i][lane, pl.ds(k, n_frames, stride=stride), :]
                   if last["staged"] else x[lane])       # (F, cin)
            if last["quantized"]:
                part = jax.lax.dot_general(
                    wq_ref[k], _quantize_tap(tap, sa), nt,
                    preferred_element_type=jnp.int32)
            else:
                part = jax.lax.dot_general(
                    w_ref[k], tap, nt, preferred_element_type=jnp.float32)
            acc = part if acc is None else acc + part
        out = (acc.astype(jnp.float32) * scale_ref[...] if last["quantized"]
               else acc)
        logits = _ACTIVATIONS[last["activation"]](
            out + bias_ref[...].astype(out.dtype))       # (C, F)
        # argmax over classes, first maximum wins (== jnp.argmax)
        best = jnp.zeros((1, n_frames), jnp.int32)
        top = logits[0:1, :]
        for c in range(1, last["cout"]):
            row = logits[c:c + 1, :]
            better = row > top
            best = jnp.where(better, c, best)
            top = jnp.where(better, row, top)
        best_ref[lane:lane + 1, :] = best

    # -------- incremental CTC collapse, lane-resident (== ctc.collapse) --
    best = jnp.where(pads_ref[...] > 0, ctc.BLANK, best_ref[...])  # (bl, F)
    prev0 = jnp.where(rmask, ctc.BLANK, prev_ref[...])  # (bl, 1)
    r = jax.lax.broadcasted_iota(jnp.int32, (n_frames, n_frames), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n_frames, n_frames), 1)
    # 0/1 GEMMs over small integers are exact at any MXU precision:
    # shifted[:, j] = best[:, j-1];  pos[:, j] = sum_{i<=j} keep[:, i] - 1
    shift = (c == r + 1).astype(jnp.float32)
    upper = (r <= c).astype(jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, best.shape, 1)
    shifted = jnp.dot(best.astype(jnp.float32), shift,
                      preferred_element_type=jnp.float32).astype(jnp.int32)
    prevs = jnp.where(col == 0, prev0, shifted)
    keep = (best != ctc.BLANK) & (best != prevs)
    keep_f = keep.astype(jnp.float32)
    lens = jnp.sum(keep.astype(jnp.int32), axis=1, keepdims=True)
    pos = jnp.dot(keep_f, upper,
                  preferred_element_type=jnp.float32).astype(jnp.int32) - 1
    vals = jnp.where(keep, best, 0).astype(jnp.float32)
    # scatter-free compaction: each kept frame lands at its unique pos, so
    # tokens[p] = sum_j vals[j] * [pos[j] == p] reproduces the collapse
    for lane in range(block_l):
        onehot = (r == pos[lane:lane + 1, :]).astype(jnp.float32)  # (p, j)
        tokens_ref[lane:lane + 1, :] = jax.lax.dot_general(
            vals[lane:lane + 1, :], onehot, nt,
            preferred_element_type=jnp.float32).astype(jnp.int32)

    # ------------------------------------------------- counter epilogue --
    lens_ref[...] = lens
    prev_out_ref[...] = best[:, n_frames - 1:]
    bases_out_ref[...] = jnp.where(rmask, 0, bases_ref[...]) + lens
    ticks_out_ref[...] = jnp.where(rmask, 0, ticks_ref[...]) + 1


def _pad_lanes(a, lanes_pad, fill=0):
    pad = lanes_pad - a.shape[0]
    if pad == 0:
        return a
    widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, widths, constant_values=fill)


def _fused_pallas(rows, pads, reset, prev, bases, ticks, conv, params, *,
                  cfg, precisions, interpret, tune):
    del precisions  # supported() already vetoed precision-policy requants
    specs = _specs(cfg)
    lanes, chunk = rows.shape
    n_frames = chunk // cfg.total_stride
    bl = min(tune["block_l"], lanes)
    lanes_pad = next_multiple(lanes, bl)

    # ---- static per-layer plan + flat operand list -----------------------
    any_int8 = False
    meta, operands, in_specs, scratch = [], [], [], []

    def add(arr, spec):
        operands.append(arr)
        in_specs.append(spec)

    def lane_spec(shape):
        return pl.BlockSpec((bl,) + shape,
                            lambda i: (i,) + (0,) * len(shape))

    def whole(shape):
        return pl.BlockSpec(shape, lambda i: (0,) * len(shape))

    add(_pad_lanes(rows, lanes_pad)[..., None], lane_spec((chunk, 1)))
    # padding lanes are all-padding frames: BLANK everywhere, lens 0
    add(_pad_lanes(pads, lanes_pad, fill=1.0), lane_spec((n_frames,)))
    reset = _pad_lanes(reset.astype(jnp.float32), lanes_pad)
    add(reset.reshape(lanes_pad, 1), lane_spec((1,)))
    add(reset.reshape(lanes_pad, 1, 1), lane_spec((1, 1)))
    for a in (prev, bases, ticks):
        add(_pad_lanes(a.reshape(lanes, 1), lanes_pad), lane_spec((1,)))
    t = chunk
    for i, sp in enumerate(specs):
        p = params[sp.name]
        w = p["w"]
        quantized = qcore.is_quantized(w)
        any_int8 = any_int8 or quantized
        is_last = i == len(specs) - 1
        staged = sp.ksize > 1 or sp.stride > 1    # carry_rows = K - stride
        meta.append({"i": i, "ksize": sp.ksize, "stride": sp.stride,
                     "carry_rows": sp.carry_rows, "cout": sp.cout,
                     "activation": sp.activation, "quantized": quantized,
                     "staged": staged})
        if sp.carry_rows:
            add(_pad_lanes(conv[i], lanes_pad),
                lane_spec((sp.carry_rows, sp.cin)))
        if staged:
            scratch.append(pltpu.VMEM((bl, sp.carry_rows + t, sp.cin),
                                      jnp.float32))
        t //= sp.stride
        # the last layer runs transposed: (K, Cout, Cin) weights and
        # per-channel vectors as (Cout, 1) columns
        vshape = (sp.cout, 1) if is_last else (1, sp.cout)
        wq = w.q if quantized else w
        if is_last:
            wq = jnp.swapaxes(wq, 1, 2)
        add(wq, whole(wq.shape))
        if quantized:
            # combined dequant scale (sa*sw) and the act scale, precomputed
            # outside — the same f32 products the unfused epilogue forms
            sa = jnp.asarray(w.act_scale, jnp.float32)
            sw = jnp.asarray(w.scale, jnp.float32)
            add(jnp.broadcast_to(sa * sw, (sp.cout,)).reshape(vshape),
                whole(vshape))
        add(p["b"].reshape(vshape), whole(vshape))
        if quantized:
            add(sa.reshape(1, 1), whole((1, 1)))
    scratch.append(pltpu.VMEM((bl, n_frames), jnp.int32))   # per-lane best

    if any_int8:
        fabric.record("fabric.precision.fused_stream.int8")

    # ---- outputs ---------------------------------------------------------
    out_shapes = [
        jax.ShapeDtypeStruct((lanes_pad, n_frames), jnp.int32),   # tokens
        jax.ShapeDtypeStruct((lanes_pad, 1), jnp.int32),          # lens
        jax.ShapeDtypeStruct((lanes_pad, 1), jnp.int32),          # prev
        jax.ShapeDtypeStruct((lanes_pad, 1), jnp.int32),          # bases
        jax.ShapeDtypeStruct((lanes_pad, 1), jnp.int32),          # ticks
    ]
    out_specs = [lane_spec((n_frames,))] + [lane_spec((1,))] * 4
    for sp in specs:
        if sp.carry_rows:
            out_shapes.append(jax.ShapeDtypeStruct(
                (lanes_pad, sp.carry_rows, sp.cin), cfg.dtype))
            out_specs.append(lane_spec((sp.carry_rows, sp.cin)))

    kernel = functools.partial(_fused_kernel_entry, meta=tuple(
        tuple(sorted(m.items())) for m in meta), block_l=bl, chunk=chunk,
        n_frames=n_frames)
    outs = pl.pallas_call(
        kernel,
        grid=(lanes_pad // bl,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*operands)

    tokens = outs[0][:lanes]
    lens = outs[1][:lanes, 0]
    new_prev = outs[2][:lanes, 0]
    new_bases = outs[3][:lanes, 0]
    new_ticks = outs[4][:lanes, 0]
    new_conv, j = [], 5
    for i, sp in enumerate(specs):
        if sp.carry_rows:
            new_conv.append(outs[j][:lanes])
            j += 1
        else:
            new_conv.append(conv[i])
    new_lane = {"conv": new_conv, "prev_class": new_prev,
                "bases": new_bases, "ticks": new_ticks}
    waste = (lanes_pad - lanes) * n_frames
    return (tokens, lens, new_lane), waste


def _fused_kernel_entry(*refs, meta, block_l, chunk, n_frames):
    # meta rides through functools.partial as a hashable tuple-of-tuples
    # (pallas traces the kernel once per static config); rehydrate dicts
    _fused_kernel(refs, meta=[dict(m) for m in meta], block_l=block_l,
                  chunk=chunk, n_frames=n_frames)


# =========================================================== registration ==
def _fused_supported(args, kwargs, tune):
    rows = args[0]
    params = args[7]
    cfg = kwargs["cfg"]
    precisions = kwargs["precisions"]
    if rows.shape[0] < 8:
        return False, "lanes_lt_8"
    if cfg.dtype != jnp.float32:
        return False, "dtype"
    for i, sp in enumerate(_specs(cfg)):
        w = params[sp.name]["w"]
        if qcore.is_quantized(w):
            if w.act_scale is None:
                return False, "int8_dynamic_act"
            if w.axis is not None and w.axis % w.ndim != w.ndim - 1:
                return False, "int8_axis"
        elif precisions[i] == "int8":
            return False, "precision_policy"
    return True, ""


def _fused_bucket(args, kwargs):
    rows = args[0]
    return f"l{_pb(rows.shape[0])}_t{_pb(rows.shape[1])}"


fabric.register_op(
    "fused_stream",
    reference=_fused_reference,
    pallas=_fused_pallas,
    tunables={"block_l": 8},
    supported=_fused_supported,
    bucket=_fused_bucket,
    reference_tune=True,
)
