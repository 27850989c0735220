"""Tensor parallelism (repro.distributed.tp) + the sharded checkpoint mode.

Three layers of coverage:

* in-process units: ``Segments`` slicing algebra, ``build_plan`` rules and
  divisibility errors, per-channel scale rules, the stacked quantize-once
  path, and the converter's ``shard_state``;
* checkpoint round-trips: QuantizedTensor params through the full and the
  sharded formats, sync and async, bitwise;
* 2-virtual-device subprocesses (XLA_FLAGS must predate jax import):
  sharded-vs-replicated ``lm_decode`` parity — bitwise for the int8 path,
  allclose(1e-5) for float32 — plus the pre-partitioned checkpoint load
  proving, by counter and by per-device shard shape, that the full weight
  never materializes.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro import quant  # noqa: E402
from repro.configs import ARCHS  # noqa: E402
from repro.distributed import tp  # noqa: E402
from repro.models.registry import get_model  # noqa: E402
from repro.train import checkpoint as ck  # noqa: E402

HERE = os.path.dirname(__file__)
SRC = os.path.abspath(os.path.join(HERE, "..", "src"))
SCRIPTS = os.path.abspath(os.path.join(HERE, "..", "scripts"))


def _run_twodev(script: str) -> dict:
    """Run a snippet under 2 virtual CPU devices, return its RESULT json."""
    # the child builds virtual CPU devices: never let it reach for a chip
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, (proc.stdout[-2000:] + proc.stderr[-4000:])
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


# ============================================================= Segments ===
class TestSegments:
    def test_plain_slice_unslice_round_trip(self):
        arr = np.arange(4 * 12, dtype=np.float32).reshape(4, 12)
        rule = tp.Segments.plain(1, 12)
        shards = [rule.slice(arr, i, 3) for i in range(3)]
        assert all(s.shape == (4, 4) for s in shards)
        np.testing.assert_array_equal(rule.unslice(shards), arr)

    def test_segment_packed_round_trip(self):
        # mamba-style [z(6) | B(2) | C(2) | dt(4)]: z/dt sharded, B/C not
        rule = tp.Segments(dim=-1, parts=((6, True), (2, False), (2, False),
                                          (4, True)))
        arr = np.random.RandomState(0).randn(3, 14).astype(np.float32)
        shards = [rule.slice(arr, i, 2) for i in range(2)]
        assert all(s.shape == (3, 3 + 2 + 2 + 2) for s in shards)
        # replicated segments appear identically on every shard
        np.testing.assert_array_equal(shards[0][:, 3:7], shards[1][:, 3:7])
        np.testing.assert_array_equal(rule.unslice(shards), arr)

    def test_local_width(self):
        rule = tp.Segments(dim=0, parts=((8, True), (2, False)))
        assert rule.local_width(2) == 6
        assert rule.local_width(4) == 4

    def test_validate_rejects_coverage_and_divisibility(self):
        rule = tp.Segments.plain(0, 8)
        with pytest.raises(ValueError, match="covers"):
            rule.validate((9,), 2, "w")
        with pytest.raises(ValueError, match="divisible"):
            tp.Segments.plain(0, 6).validate((6,), 4, "w")

    def test_json_round_trip(self):
        rule = tp.Segments(dim=2, parts=((6, True), (2, False)))
        assert tp.Segments.from_json(rule.to_json()) == rule
        assert tp.Segments.from_json("replicated") is None
        assert tp.rule_to_json(None) == "replicated"

    def test_negative_dim_slices_last(self):
        arr = np.arange(2 * 3 * 8, dtype=np.float32).reshape(2, 3, 8)
        rule = tp.Segments.plain(-1, 8)
        got = rule.slice(arr, 1, 2)
        np.testing.assert_array_equal(got, arr[..., 4:])


# ============================================================ build_plan ==
def _plan(arch="qwen3-4b", tp_degree=2, **over):
    cfg = ARCHS[arch].smoke_config()
    if over:
        cfg = dataclasses.replace(cfg, **over)
    model = get_model(cfg)
    shapes, axes = model.abstract_params(cfg)
    return tp.build_plan(axes, shapes, cfg=cfg, tp=tp_degree), cfg


class TestBuildPlan:
    def test_qwen3_rules(self):
        plan, cfg = _plan()
        flat = plan.flat_json()
        # column-parallel: last (output) dim of the stacked (nb, in, out)
        assert flat["blocks/l0/attn/wq"]["dim"] == 2
        assert flat["blocks/l0/mlp/wi"]["dim"] == 2
        # row-parallel: the input dim
        assert flat["blocks/l0/attn/wo"]["dim"] == 1
        assert flat["blocks/l0/mlp/wo"]["dim"] == 1
        # vocab-parallel embedding; norms replicated
        assert flat["embedding/embed"]["dim"] == 0
        assert flat["blocks/l0/norm1/scale"] == "replicated"
        assert flat["final_norm/scale"] == "replicated"

    def test_mamba_segments(self):
        plan, cfg = _plan("mamba2-780m")
        di, ds, nh = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_heads
        rule = plan.flat["blocks/l0/mamba/in_proj"]
        assert rule.parts == ((di, True), (di, True), (ds, False),
                              (ds, False), (nh, True))
        conv = plan.flat["blocks/l0/mamba/conv_w"]
        assert conv.parts == ((di, True), (ds, False), (ds, False))
        # per-head vectors shard with the heads
        assert plan.flat["blocks/l0/mamba/A_log"] is not None
        assert plan.flat["blocks/l0/mamba/D"] is not None

    def test_indivisible_heads_raise_with_names(self):
        with pytest.raises(ValueError, match="num_heads"):
            _plan(tp_degree=3)

    def test_odd_vocab_falls_back_to_replicated(self):
        plan, _ = _plan(vocab_size=255)
        assert plan.flat["embedding/embed"] is None
        # the rest of the model still shards
        assert plan.flat["blocks/l0/mlp/wi"] is not None

    def test_moe_experts_stay_replicated(self):
        plan, _ = _plan("grok-1-314b")
        assert all(r is None for k, r in plan.flat.items() if "moe" in k)

    def test_tp1_is_all_replicated(self):
        plan, _ = _plan(tp_degree=1)
        assert all(r is None for r in plan.flat.values())


class TestScaleRule:
    def test_column_parallel_scale_slices(self):
        rule = tp.Segments.plain(2, 8)          # (nb, in, out) sliced on out
        sr = tp.scale_rule(rule, 3)
        assert sr is not None and sr.dim == -1 and sr.parts == rule.parts

    def test_row_parallel_scale_replicates(self):
        assert tp.scale_rule(tp.Segments.plain(1, 8), 3) is None

    def test_replicated_passthrough(self):
        assert tp.scale_rule(None, 3) is None


# ===================================================== stacked quantize ===
class TestStackedQuantize:
    def test_scales_carry_the_stack_dim(self):
        params = {"blocks": {"l0": {"mlp": {
            "wi": np.random.RandomState(0).randn(3, 8, 16).astype(np.float32),
        }}}}
        qp = quant.quantize_params(params, stack_dims=1)
        qt = qp["blocks"]["l0"]["mlp"]["wi"]
        assert qt.q.shape == (3, 8, 16)
        assert qt.scale.shape == (3, 16)        # per (block, channel)
        assert qt.axis == -1

    def test_scan_peels_payload_and_scale_together(self):
        w = np.random.RandomState(1).randn(4, 8, 16).astype(np.float32)
        qt = quant.quantize_tensor(jnp.asarray(w), axis=2, stack_dims=1)

        def body(_, block_qt):
            return None, block_qt.dequantize()

        _, deq = jax.lax.scan(body, None, qt)
        np.testing.assert_allclose(np.asarray(deq), w, atol=np.abs(w).max() / 100)

    def test_per_block_scales_beat_shared_scales(self):
        rs = np.random.RandomState(2)
        w = np.concatenate([rs.randn(1, 8, 16), 100 * rs.randn(1, 8, 16)],
                           0).astype(np.float32)
        stacked = quant.quantize_tensor(jnp.asarray(w), axis=2, stack_dims=1)
        shared = quant.quantize_tensor(jnp.asarray(w), axis=2)
        # error on the small block: shared scales are set by the 100x block
        err = lambda qt: float(
            np.abs(np.asarray(qt.dequantize())[0] - w[0]).max())
        assert err(stacked) < err(shared) / 10


# =========================================================== shard_state ==
class TestShardState:
    def test_quantized_leaves_slice_payload_and_scales(self):
        plan, cfg = _plan()
        model = get_model(dataclasses.replace(cfg, dtype="float32"))
        params, _ = model.init(jax.random.key(0),
                               dataclasses.replace(cfg, dtype="float32"))
        qp = jax.device_get(quant.quantize_params(params, stack_dims=1))
        flat = dict(ck._flatten(qp)[0])
        shards, info = tp.shard_state(flat, plan)
        assert len(shards) == 2
        wi = "blocks/l0/mlp/wi"
        full_q, full_s = flat[wi + "/0"], flat[wi + "/1"]
        for m in (0, 1):
            assert shards[m][wi + "/0"].shape[-1] == full_q.shape[-1] // 2
            assert shards[m][wi + "/1"].shape[-1] == full_s.shape[-1] // 2
        # column-parallel: scale sliced along the same axis as the payload
        np.testing.assert_array_equal(shards[1][wi + "/1"],
                                      full_s[..., full_s.shape[-1] // 2:])
        # row-parallel wo: payload sliced on the input dim, scale replicated
        wo = "blocks/l0/mlp/wo"
        assert info[wo + "/1"] == "replicated"
        np.testing.assert_array_equal(shards[0][wo + "/1"],
                                      shards[1][wo + "/1"])

    def test_unknown_keys_replicate(self):
        plan, _ = _plan()
        shards, info = tp.shard_state(
            {"opt/step": np.asarray(3)}, plan)
        assert info["opt/step"] == "replicated"
        assert shards[0]["opt/step"] == 3

    def test_prefix_stripping(self):
        plan, cfg = _plan()
        w = np.zeros((cfg.num_blocks, cfg.d_model, cfg.d_ff), np.float32)
        shards, info = tp.shard_state({"params/blocks/l0/mlp/wi": w}, plan,
                                      prefix="params")
        assert info["params/blocks/l0/mlp/wi"] != "replicated"
        assert shards[0]["params/blocks/l0/mlp/wi"].shape[-1] == cfg.d_ff // 2


# ================================================================= rope ===
def test_rope_rejects_odd_head_dim():
    from repro.models import layers as L
    x = jnp.zeros((1, 4, 2, 5))
    with pytest.raises(ValueError, match="even head_dim"):
        L.rope(x, jnp.zeros((1, 4), jnp.int32), theta=1e4)


# ========================================== checkpoint: QT round trips ====
def _quantized_state():
    cfg = dataclasses.replace(ARCHS["qwen3-4b"].smoke_config(),
                              dtype="float32")
    model = get_model(cfg)
    params, _ = model.init(jax.random.key(0), cfg)
    return jax.device_get(quant.quantize_params(params, stack_dims=1)), cfg


def _assert_qt_trees_bitwise(got, want):
    fg, _ = ck._flatten(got)
    fw, _ = ck._flatten(want)
    fg, fw = dict(fg), dict(fw)
    assert set(fg) == set(fw)
    for k in fw:
        assert fg[k].dtype == fw[k].dtype, k
        np.testing.assert_array_equal(fg[k], fw[k], err_msg=k)


class TestQuantizedCheckpointRoundTrip:
    def test_full_format_sync(self, tmp_path):
        qp, _ = _quantized_state()
        d = str(tmp_path / "ck")
        ck.save(d, qp, step=3)
        got, step = ck.load_params(d)
        assert step == 3
        _assert_qt_trees_bitwise(got, qp)
        qt = got["blocks"]["l0"]["mlp"]["wi"]
        assert quant.is_quantized(qt) and qt.q.dtype == np.int8
        assert qt.axis == -1

    def test_full_format_async_matches_sync(self, tmp_path):
        qp, _ = _quantized_state()
        sync_d, async_d = str(tmp_path / "s"), str(tmp_path / "a")
        ck.save(sync_d, qp, step=5)
        ck.save_async(async_d, qp, step=5)
        ck.wait_pending()
        a, _ = ck.load_params(sync_d)
        b, _ = ck.load_params(async_d)
        _assert_qt_trees_bitwise(a, b)

    def test_sharded_format_round_trips_bitwise(self, tmp_path):
        qp, cfg = _quantized_state()
        model = get_model(cfg)
        shapes, axes = model.abstract_params(cfg)
        plan = tp.build_plan(axes, shapes, cfg=cfg, tp=2)
        flat = dict(ck._flatten(qp)[0])
        shards, info = tp.shard_state(flat, plan)
        d = str(tmp_path / "tp2")
        ck.save_sharded(d, shards, 9, shard_info=info)
        manifest, _ = ck._read_manifest(d, None)
        assert manifest["format"] == "sharded"
        assert manifest["num_shards"] == 2
        # restore reassembles the full tree bit-identically
        got, step = ck.load_params(d)
        assert step == 9
        _assert_qt_trees_bitwise(got, qp)

    def test_restore_closes_npz_handle(self, tmp_path):
        qp, _ = _quantized_state()
        d = str(tmp_path / "ck")
        ck.save(d, qp, step=1)
        ck.load_params(d)
        if os.path.isdir("/proc/self/fd"):
            open_files = []
            for fd in os.listdir("/proc/self/fd"):
                try:
                    open_files.append(os.readlink(f"/proc/self/fd/{fd}"))
                except OSError:
                    pass
            assert not [f for f in open_files if f.endswith(".npz")]

    def test_gc_skips_in_flight_steps(self, tmp_path):
        d = str(tmp_path / "ck")
        for s in range(4):
            ck.save(d, {"w": np.zeros(3, np.float32)}, step=s, keep_last=10)
        token = (os.path.abspath(d), "step_00000001")
        ck._IN_FLIGHT.add(token)
        try:
            with ck._LOCK:
                ck._gc(d, keep_last=1)
            left = sorted(n for n in os.listdir(d) if n.startswith("step_"))
            # newest kept, the in-flight step survives, the rest collected
            assert left == ["step_00000001", "step_00000003"]
        finally:
            ck._IN_FLIGHT.discard(token)

    def test_sharded_rejected_by_read_sharded_on_full(self, tmp_path):
        d = str(tmp_path / "ck")
        ck.save(d, {"w": np.zeros(3, np.float32)}, step=0)
        with pytest.raises(ValueError, match="sharded"):
            ck.read_sharded(d)


# ============================================ 2-device subprocess tests ===
_PARITY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import dataclasses, json, sys
sys.path.insert(0, {src!r})
import numpy as np, jax, jax.numpy as jnp
from repro.configs import ARCHS
from repro.engine.registry import build
from repro.models.registry import get_model
from repro import quant

def decode_logits(eng, steps):
    toks = np.array([[3], [5]], np.int32)
    pos = np.zeros((2,), np.int32)
    out = []
    for _ in range(steps):
        l, eng.cache = eng._step(eng.params, eng.cache, jnp.asarray(toks),
                                 jnp.asarray(pos))
        l = np.asarray(jax.device_get(l))
        out.append(l)
        pos += 1
        toks = l[:, -1].argmax(-1)[:, None].astype(np.int32)
    return out

out = {{}}
for arch, steps, quantized in (("qwen3-4b", 8, False), ("qwen3-4b", 8, True),
                               ("mamba2-780m", 6, False)):
    cfg = dataclasses.replace(ARCHS[arch].smoke_config(), dtype="float32")
    model = get_model(cfg)
    params, _ = model.init(jax.random.key(0), cfg)
    if quantized:
        params = quant.quantize_params(params, stack_dims=1)
    ref = build("lm_decode", model=model, params=params, cfg=cfg,
                slots=2, max_len=16)
    tp2 = build("lm_decode", model=model, params=params, cfg=cfg,
                slots=2, max_len=16, mesh=2)
    assert tp2.tp == 2
    lr = decode_logits(ref, steps)
    lt = decode_logits(tp2, steps)
    key = arch + ("/int8" if quantized else "/f32")
    if quantized:
        out[key] = {{"bitwise": all(np.array_equal(a, b)
                                    for a, b in zip(lr, lt)),
                     "tokens_match": all(
                         np.array_equal(a[:, -1].argmax(-1),
                                        b[:, -1].argmax(-1))
                         for a, b in zip(lr, lt))}}
    else:
        worst = 0.0
        ok = True
        for a, b in zip(lr, lt):
            worst = max(worst, float(np.abs(a - b).max()))
            ok &= bool(np.all(np.abs(a - b) <= 1e-5 + 1e-5 * np.abs(a)))
        out[key] = {{"allclose": ok, "worst": worst}}
print("RESULT " + json.dumps(out))
"""


def test_sharded_vs_replicated_parity_two_devices():
    """The pinned parity criterion: lm_decode on a (data=1, model=2)
    virtual mesh matches the unsharded oracle — bitwise for the
    quantize-once int8 path, allclose(1e-5) for float32, attention and
    Mamba-2 stacks both."""
    out = _run_twodev(_PARITY_SCRIPT.format(src=SRC))
    assert out["qwen3-4b/int8"]["bitwise"] is True
    assert out["qwen3-4b/int8"]["tokens_match"] is True
    assert out["qwen3-4b/f32"]["allclose"] is True, out["qwen3-4b/f32"]
    assert out["mamba2-780m/f32"]["allclose"] is True, out["mamba2-780m/f32"]


_SHARDED_LOAD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import dataclasses, json, sys, tempfile
sys.path.insert(0, {src!r})
sys.path.insert(0, {scripts!r})
import numpy as np, jax, jax.numpy as jnp
from repro.configs import ARCHS
from repro.engine.registry import build
from repro.models.registry import get_model
from repro import quant
from repro.train import checkpoint as ck
from repro.kernels import fabric
from checkpoint_converter import convert

cfg = dataclasses.replace(ARCHS["qwen3-4b"].smoke_config(), dtype="float32")
model = get_model(cfg)
params, _ = model.init(jax.random.key(0), cfg)
qp = quant.quantize_params(params, stack_dims=1)

tmp = tempfile.mkdtemp()
full_dir, shard_dir = os.path.join(tmp, "full"), os.path.join(tmp, "tp2")
ck.save(full_dir, jax.device_get(qp), step=7)
convert(full_dir, shard_dir, tp=2, arch="qwen3-4b", smoke=True)

out = {{}}
# full -> sharded -> restored round-trips bit-identically
m1, flat1 = ck._load_flat(full_dir, None, True)
m2, flat2 = ck._load_flat(shard_dir, None, True)
out["round_trip_bitwise"] = (set(flat1) == set(flat2) and all(
    flat1[k].dtype == flat2[k].dtype and np.array_equal(flat1[k], flat2[k])
    for k in flat1))

# pre-partitioned load: counted, and no device holds a full sharded weight
base = dict(fabric.counters())
eng = build("lm_decode", model=model, cfg=cfg, slots=2, max_len=16,
            mesh=2, ckpt_dir=shard_dir)
delta = {{k: v - base.get(k, 0) for k, v in fabric.counters().items()
          if k.startswith("tp.load.")}}
out["counters"] = delta
wi = eng.params["blocks"]["l0"]["mlp"]["wi"]
out["device_local_cols"] = sorted(
    s.data.shape[-1] for s in wi.q.addressable_shards)
out["full_cols"] = int(wi.q.shape[-1])

# the migration path (full checkpoint into a TP mesh) counts the slice path
base = dict(fabric.counters())
eng_mig = build("lm_decode", model=model, cfg=cfg, slots=2, max_len=16,
                mesh=2, ckpt_dir=full_dir)
mig = {{k: v - base.get(k, 0) for k, v in fabric.counters().items()
        if k.startswith("tp.load.")}}
out["migration_counters"] = mig

# and the checkpoint-loaded TP engine serves bitwise vs the oracle
ref = build("lm_decode", model=model, params=qp, cfg=cfg, slots=2,
            max_len=16)
toks = np.array([[3], [5]], np.int32)
pos = np.zeros((2,), np.int32)
bitwise = True
for _ in range(6):
    lr, ref.cache = ref._step(ref.params, ref.cache, jnp.asarray(toks),
                              jnp.asarray(pos))
    lt, eng.cache = eng._step(eng.params, eng.cache, jnp.asarray(toks),
                              jnp.asarray(pos))
    bitwise &= bool(np.array_equal(np.asarray(lr), np.asarray(lt)))
    pos += 1
    toks = np.asarray(lr)[:, -1].argmax(-1)[:, None].astype(np.int32)
out["serve_bitwise"] = bitwise

# a checkpoint converted for the wrong tp degree is rejected, not re-sliced
wrong_dir = os.path.join(tmp, "tp1")
flat, _ = ck._flatten(jax.device_get(qp))
ck.save_sharded(wrong_dir, [dict(flat)], 7, shard_info={{}})
try:
    build("lm_decode", model=model, cfg=cfg, slots=2, max_len=16,
          mesh=2, ckpt_dir=wrong_dir)
    out["wrong_tp_rejected"] = False
except ValueError as e:
    out["wrong_tp_rejected"] = "re-run the converter" in str(e)
print("RESULT " + json.dumps(out))
"""


def test_sharded_checkpoint_loads_pre_partitioned_two_devices():
    """format:"sharded" checkpoints load pre-partitioned: the
    ``tp.load.pre_partitioned`` counter fires, ``replicated_slice`` does
    not, each device's addressable shard holds exactly the local block —
    the full weight never materializes — and the engine still serves
    bitwise against the replicated oracle."""
    out = _run_twodev(_SHARDED_LOAD_SCRIPT.format(src=SRC, scripts=SCRIPTS))
    assert out["round_trip_bitwise"] is True
    assert out["counters"].get("tp.load.pre_partitioned", 0) > 0
    assert out["counters"].get("tp.load.replicated_slice", 0) == 0
    assert out["device_local_cols"] == [out["full_cols"] // 2] * 2
    assert out["migration_counters"].get("tp.load.replicated_slice", 0) > 0
    assert out["migration_counters"].get("tp.load.pre_partitioned", 0) == 0
    assert out["serve_bitwise"] is True
    assert out["wrong_tp_rejected"] is True


_PARALLEL_CE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import dataclasses, json, sys
sys.path.insert(0, {src!r})
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.configs import ARCHS
from repro.models.registry import get_model
from repro.models import transformer
from repro.distributed import tp, sharding as shardlib
from repro.launch.mesh import make_mesh

cfg = dataclasses.replace(ARCHS["qwen3-4b"].smoke_config(), dtype="float32")
model = get_model(cfg)
params, _ = model.init(jax.random.key(0), cfg)
rs = np.random.RandomState(0)
batch = {{"tokens": jnp.asarray(rs.randint(0, 256, (2, 8))),
          "labels": jnp.asarray(rs.randint(0, 256, (2, 8)))}}
loss_ref, _ = transformer.loss_fn(params, batch, cfg)

mesh = make_mesh((1, 2), ("data", "model"))
shapes, axes = model.abstract_params(cfg)
plan = tp.build_plan(axes, shapes, cfg=cfg, tp=2,
                     rules=shardlib.default_rules(mesh))
tparams = tp.partition_params(params, mesh, plan)

def local_loss(p, b):
    with tp.axis_ctx("model", 2):
        return transformer.loss_fn(p, b, cfg)

f = jax.jit(jax.shard_map(
    local_loss, mesh=mesh, in_specs=(tp.param_pspecs(plan, tparams), P()),
    out_specs=(P(), P()), check_vma=False))
loss_tp, _ = f(tparams, batch)
print("RESULT " + json.dumps(
    {{"ref": float(loss_ref), "tp": float(loss_tp)}}))
"""


def test_parallel_cross_entropy_two_devices():
    """Sharded-softmax CE over vocab-parallel logits matches the oracle
    log_softmax loss — the full logit row never materializes in the
    training path."""
    out = _run_twodev(_PARALLEL_CE_SCRIPT.format(src=SRC))
    assert abs(out["ref"] - out["tp"]) <= 1e-5, out
