"""Offline driver: long reads through the batch basecall engine.

Set-up cuts the mix's pool of long reads (fixed by its ``pool_seed``) into
overlapping chunks, lays the pool out as whole batches in an order drawn
from the seed, and asks the configuration's model family
(``bench.configs.model_of``) for the weights, made on the device, and the
program's engine, ``repro.engine.build("basecall", batch=B, chunk=C)``
for the conv stack.  Warm-up sends
every batch of the pool through ``engine.step()`` once, so that every
program the window will run is compiled.  The window then submits the
batches in turn and calls ``engine.step()`` back to back.

The check basecalls a seeded sample of the window's rows (with the row of
most bases among them) again with the family's plain reference and
compares the tokens the engine returned for them.
"""
from __future__ import annotations

import gc
import time

import jax
import numpy as np

from bench.configs import model_of
from bench.lib import chunker, common


def setup(run, cfg: dict, traffic: dict):
    model = model_of(cfg)
    b = traffic["batch"]
    with run.clock.phase("synthesis"):
        # one pool of reads for every seed, so that every seed basecalls
        # the same work and only the order of its batches differs
        rows, counted = chunker.long_read_chunks(
            np.random.default_rng(traffic["pool_seed"]), traffic,
            cfg["signal"])
        n_batches = len(rows) // b
        order = np.random.default_rng([run.seed, 1]).permutation(
            len(rows))[:n_batches * b]
        batches = [order[i * b:(i + 1) * b] for i in range(n_batches)]
        inputs = [np.ascontiguousarray(rows[idx]) for idx in batches]
    with run.clock.phase("weights"):
        params = model.make_params(cfg, run.seed)
        jax.block_until_ready(params)
    with run.clock.phase("build"):
        eng = model.offline_engine(params, cfg, batch=b,
                                   chunk=traffic["chunk"], seed=run.seed)
    with run.clock.phase("warmup"):
        for x in inputs:
            eng.submit(x)
            eng.step()
        eng.reads.clear()
        jax.effects_barrier()
    return {"eng": eng, "rows": rows, "counted": counted,
            "batches": batches, "inputs": inputs}


def window(run, state: dict, traffic: dict) -> dict:
    eng = state["eng"]
    tel = eng.telemetry
    if run.trace_dir is not None:
        common.traced_stages(tel)
    new = [int(state["counted"][idx].sum()) for idx in state["batches"]]
    stage0 = dict(tel.stage_s)
    fabric0 = tel.fabric_counters()
    compiles0 = run.clock.compiles
    order, step_s, samples = [], 0.0, 0
    with common.profiled(run.trace_dir):
        with common.annotate("bench.window"):
            t0 = time.perf_counter()
            i = 0
            while time.perf_counter() - t0 < run.seconds:
                j = i % len(state["inputs"])
                with common.annotate("bench.submit"):
                    eng.submit(state["inputs"][j])
                s = time.perf_counter()
                with common.annotate("bench.step"):
                    eng.step()
                step_s += time.perf_counter() - s
                samples += new[j]
                order.append(j)
                i += 1
            t1 = time.perf_counter()
    jax.effects_barrier()
    return {
        "window_s": t1 - t0,
        "batches_run": len(order),
        "order": order,
        "offline_samples": samples,
        "step_s": step_s,
        "stage_s": {n: tel.stage_s.get(n, 0.0) - stage0.get(n, 0.0)
                    for n in tel.stage_s},
        "fabric": {k: v - fabric0.get(k, 0)
                   for k, v in tel.fabric_counters().items()
                   if v - fabric0.get(k, 0)},
        "compiles_in_window": run.clock.compiles - compiles0,
    }


def check_rows(seed, reads: list, order: list, batches, rows, cfg: dict,
               traffic: dict, *, operands: str = "f32") -> dict:
    """The engine's tokens for a seeded sample of the window's rows against
    the reference's (or, with ``operands``, the control's)."""
    b = traffic["batch"]
    rng = np.random.default_rng([seed, 3])
    n = min(traffic["check_rows"], len(reads))
    if n == 0:
        return {"attempted": 0, "failed": 0, "checks": {}}
    pick = set(rng.choice(len(reads), size=n, replace=False).tolist())
    pick.add(int(np.argmax([len(r) for r in reads])))
    pick = sorted(pick)
    row_ids = [int(batches[order[p // b]][p % b]) for p in pick]
    x = rows[row_ids]
    model = model_of(cfg)
    params = model.make_params(cfg, seed)
    want = model.offline_reads(params, cfg, x, operands="f32")
    got = [np.asarray(reads[p]) for p in pick] if operands == "f32" else \
        model.offline_reads(params, cfg, x, operands=operands)
    rate, n_tok, n_diff = common.token_mismatch(got, want)
    limit = cfg["limits"]["offline"]["token_mismatch"]
    return {"attempted": len(pick), "failed": 0,
            "checks": {"token_mismatch": {"value": rate, "limit": limit}},
            "reference_tokens": n_tok, "rows_differ": n_diff}


def evidence(state: dict, win: dict) -> dict:
    """What the check needs of a run, free of the engine."""
    return {"reads": list(state["eng"].reads), "order": win["order"],
            "batches": state["batches"], "rows": state["rows"]}


def check(seed: int, ev: dict, cfg: dict, traffic: dict,
          operands: str = "f32") -> dict:
    return check_rows(seed, ev["reads"], ev["order"], ev["batches"],
                      ev["rows"], cfg, traffic, operands=operands)


def run(run, cfg: dict, traffic: dict, control: str | None = None) -> dict:
    state = setup(run, cfg, traffic)
    run.setup_done()
    win = window(run, state, traffic)
    run.after_window(win)
    out = dict(win)
    out["chips_used"] = 1
    out["samples"] = win["offline_samples"]
    out["batch"] = traffic["batch"]
    out["chunk"] = traffic["chunk"]
    ev = evidence(state, win)
    del state
    gc.collect()
    out["check"] = check(run.seed, ev, cfg, traffic,
                         operands=control or "f32")
    if control is not None:
        out["program_check"] = check(run.seed, ev, cfg, traffic)
    return out
