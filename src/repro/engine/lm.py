"""LM decode engine: continuous batching over a fixed KV-slot pool.

The decode_32k / long_500k serving shape: a fixed pool of KV-cache slots,
requests admitted into free slots (prefill token-by-token, simple and
exact), every ``step`` advancing *all* active slots one token, finished
slots freeing immediately.  The slot bookkeeping that ``LMServer`` carried
privately now lives in the shared :class:`~repro.engine.scheduler.SlotScheduler`;
latency/throughput accounting lives in :class:`~repro.engine.telemetry.Telemetry`.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.base import EngineBase
from repro.engine.registry import register


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray           # (L,) tokens
    max_new_tokens: int
    submitted_at: float = 0.0
    tokens_out: list = dataclasses.field(default_factory=list)
    done_at: float = 0.0


def _resolve_mesh(mesh):
    """None | int tp degree | Mesh -> Mesh or None."""
    if mesh is None or isinstance(mesh, jax.sharding.Mesh):
        return mesh
    tp_degree = int(mesh)
    if tp_degree <= 1:
        return None
    from repro.launch.mesh import make_mesh
    return make_mesh((1, tp_degree), ("data", "model"))


# which dim of each cache leaf is model-sharded: k/v/conv shard their
# packed feature dim (last), the ssm state its packed batch*heads rows
_CACHE_TP_DIM = {"k": -1, "v": -1, "conv": -1, "ssm": 2}


class LMDecodeEngine(EngineBase):
    """Slot-based continuous batching around a jitted serve_step.

    ``mesh`` (a Mesh with a ``model`` axis, or an int tensor-parallel
    degree) shards the model Megatron-style: params are partitioned per
    :mod:`repro.distributed.tp`, the per-shard KV/SSM caches are created
    inside shard_map (never materialized whole), and ``_step`` becomes a
    shard_map'd serve with the gathered logits replicated on the host
    side — the decode loop is byte-for-byte the replicated one.

    ``ckpt_dir`` loads params from a checkpoint: a ``format: "sharded"``
    checkpoint (from scripts/checkpoint_converter.py) loads
    pre-partitioned — each device only ever receives its slice; a full
    checkpoint is the migration path (replicated load, then slice)."""

    workload = "lm_decode"

    def __init__(self, model, params, cfg, *, slots: int, max_len: int,
                 eos: int = -1, fabric=None, trace=False, mesh=None,
                 ckpt_dir=None, ckpt_step=None):
        from repro.kernels import fabric as fabric_mod
        super().__init__(slots=slots, tracer=trace)
        self.model = model
        self.cfg = cfg
        self.max_len = max_len
        self.eos = eos
        self.fabric = fabric_mod.as_policy(fabric)
        self.mesh = _resolve_mesh(mesh)
        self.tp = (int(self.mesh.shape.get("model", 1))
                   if self.mesh is not None else 1)
        self.plan = None
        if self.tp > 1:
            self._build_tensor_parallel(params, ckpt_dir, ckpt_step)
        else:
            if params is None and ckpt_dir is not None:
                from repro.train import checkpoint as ck
                params, _ = ck.load_params(ckpt_dir, step=ckpt_step)
            self.params = params
            self.cache = model.init_cache(cfg, slots, max_len)

            def _serve(p, c, t, pos):
                # model layers read the fabric policy at trace time; this
                # jit is per-engine, so the placement is pinned per engine
                with fabric_mod.use(self.fabric):
                    return model.serve(p, c, t, pos, cfg)

            self._step = jax.jit(_serve)
        self.pos = np.zeros((slots,), np.int32)
        self.budget = np.zeros((slots,), np.int32)  # remaining new tokens
        self.finished: list[Request] = []

    def _build_tensor_parallel(self, params, ckpt_dir, ckpt_step):
        from jax.sharding import PartitionSpec as P
        from repro.distributed import sharding as shardlib
        from repro.distributed import tp as tp_mod
        from repro.kernels import fabric as fabric_mod
        model, cfg, mesh, ext = self.model, self.cfg, self.mesh, self.tp
        shapes, axes = model.abstract_params(cfg)
        plan = tp_mod.build_plan(axes, shapes, cfg=cfg, tp=ext,
                                 rules=shardlib.default_rules(mesh))
        self.plan = plan
        if params is None and ckpt_dir is not None:
            from repro.train import checkpoint as ck
            manifest, _ = ck._read_manifest(ckpt_dir, ckpt_step)
            if manifest.get("format") == "sharded":
                params = tp_mod.load_sharded_params(ckpt_dir, mesh, plan,
                                                    step=ckpt_step)
            else:
                # migration path: full checkpoint, replicated then sliced
                params, _ = ck.load_params(ckpt_dir, step=ckpt_step)
                params = tp_mod.partition_params(params, mesh, plan)
        elif params is not None:
            params = tp_mod.partition_params(params, mesh, plan)
        else:
            raise ValueError("tensor-parallel engine needs params or "
                             "ckpt_dir")
        self.params = params

        slots, max_len = self.scheduler.slots, self.max_len

        def _local_cache():
            with tp_mod.axis_ctx("model", ext):
                return model.init_cache(cfg, slots, max_len)

        with tp_mod.axis_ctx("model", ext):
            cache_like = jax.eval_shape(_local_cache)
        cache_specs = {
            name: P(*("model" if i == _CACHE_TP_DIM[name] % leaf.ndim
                      else None for i in range(leaf.ndim)))
            for name, leaf in cache_like.items()}
        self.cache = jax.jit(jax.shard_map(
            _local_cache, mesh=mesh, in_specs=(), out_specs=cache_specs,
            check_vma=False))()

        param_specs = tp_mod.param_pspecs(plan, params)

        def _serve(p, c, t, pos):
            with fabric_mod.use(self.fabric), \
                    tp_mod.axis_ctx("model", ext):
                return model.serve(p, c, t, pos, cfg)

        self._step = jax.jit(jax.shard_map(
            _serve, mesh=mesh,
            in_specs=(param_specs, cache_specs, P(), P()),
            out_specs=(P(), cache_specs), check_vma=False))

    @property
    def slots(self) -> int:
        return self.scheduler.slots

    def _slot_tid(self, s: int) -> int:
        return self.telemetry.tracer.tid(self.telemetry.trace_pid,
                                         f"slot{s:02d}")

    def submit(self, req: Request, **_) -> None:
        req.submitted_at = time.perf_counter()
        self.scheduler.submit(req)

    def _admit(self) -> None:
        tracer, pid = self.telemetry.tracer, self.telemetry.trace_pid
        for s, req in self.scheduler.admit():
            if tracer.enabled:
                # per-request lifecycle span on the slot's own track,
                # closed when the request finishes (see step)
                tracer.begin("request", pid=pid, tid=self._slot_tid(s),
                             cat="request",
                             args={"uid": req.uid,
                                   "prompt_len": len(req.prompt),
                                   "max_new_tokens": req.max_new_tokens})
            # prefill: feed prompt tokens one by one (simple, exact)
            logits = None
            with self.telemetry.stage("prefill"):
                for tok in req.prompt:
                    tkn = jnp.full((self.slots, 1), 0, jnp.int32).at[s, 0].set(
                        int(tok))
                    pos = jnp.asarray(self.pos)
                    logits, self.cache = self._step(self.params, self.cache,
                                                    tkn, pos)
                    self.telemetry.dispatches += 1
                    self.pos[s] += 1
            self.budget[s] = req.max_new_tokens
            if logits is not None:
                req.tokens_out.append(int(jnp.argmax(logits[s, -1])))
            # empty prompt: the first decode step() seeds from token 0

    def step(self) -> bool:
        """One decode step across all active slots."""
        t0 = time.perf_counter()
        with self.telemetry.scope():
            self._admit()
            active = self.scheduler.active
            if self.scheduler.n_busy == 0:
                return False
            toks = np.zeros((self.slots, 1), np.int32)
            for s, req in enumerate(active):
                if req is not None and req.tokens_out:
                    toks[s, 0] = req.tokens_out[-1]
            with self.telemetry.stage("decode"):
                logits, self.cache = self._step(self.params, self.cache,
                                                jnp.asarray(toks),
                                                jnp.asarray(self.pos))
                logits_np = np.asarray(logits[:, -1])
        tracer, pid = self.telemetry.tracer, self.telemetry.trace_pid
        self.telemetry.dispatches += 1
        self.telemetry.steps += 1
        for s, req in enumerate(active):
            if req is None:
                continue
            self.pos[s] += 1
            self.budget[s] -= 1
            nxt = int(logits_np[s].argmax())
            req.tokens_out.append(nxt)
            self.telemetry.tokens += 1
            hit_eos = (self.eos >= 0 and nxt == self.eos)
            if self.budget[s] <= 0 or hit_eos \
                    or self.pos[s] >= self.max_len - 1:
                req.done_at = time.perf_counter()
                self.finished.append(req)
                self.scheduler.release(s)
                self.pos[s] = 0
                self.telemetry.completed += 1
                self.telemetry.observe_latency(
                    (req.done_at - req.submitted_at) * 1e3)
                if tracer.enabled:
                    tracer.end(pid=pid, tid=self._slot_tid(s),
                               args={"tokens": len(req.tokens_out),
                                     "eos": hit_eos})
        self.telemetry.gauge("queue_depth", self.scheduler.pending)
        self.telemetry.gauge("slots_busy", self.scheduler.n_busy)
        self.telemetry.wall_s += time.perf_counter() - t0
        return True


@register("lm_decode", presets={
    "default": {"slots": 4, "max_len": 64},
    "smoke": {"slots": 2, "max_len": 32},
    "full": {"smoke": False, "slots": 8, "max_len": 512},
})
def build_lm_decode(model=None, params=None, cfg=None, *,
                    arch: str = "qwen3-4b", smoke: bool = True,
                    slots: int, max_len: int, eos: int = -1, fabric=None,
                    seed: int = 0, trace=False, mesh=None, ckpt_dir=None,
                    ckpt_step=None):
    """Builder: supply (model, params, cfg) or let the preset pick an arch
    (smoke config by default) and initialize fresh params.

    ``mesh`` (Mesh with a ``model`` axis, or an int tp degree) enables
    tensor-parallel serving; ``ckpt_dir`` loads params from a checkpoint
    (a sharded one loads pre-partitioned) instead of initializing."""
    if cfg is None:
        from repro.configs import ARCHS
        spec = ARCHS[arch]
        cfg = spec.smoke_config() if smoke else spec.config()
    if model is None:
        from repro.models.registry import get_model
        model = get_model(cfg)
    if params is None and ckpt_dir is None:
        params, _ = model.init(jax.random.key(seed), cfg)
    return LMDecodeEngine(model, params, cfg, slots=slots, max_len=max_len,
                          eos=eos, fabric=fabric, trace=trace, mesh=mesh,
                          ckpt_dir=ckpt_dir, ckpt_step=ckpt_step)
