"""Device time of the expert-share training step's layers (the ``train_ep``
traffic kind), read through the program's scopes.

``bench/scopes.py`` reads the train step's five scopes for the
``train_steps`` kind.  The expert-share step runs the same step with more
scopes inside ``grad``: :data:`EP_SCOPES`, named in
``repro.models.attention.apply_mla`` and ``repro.models.moe.
apply_moe_dropless``.  This module reuses ``bench/scopes.py``'s HLO map and
attribution with those scopes added to its list for the length of one
attribution, and finds the cell's programs among the ``train_ep`` mixes;
an op takes its innermost scope, so ``grad`` keeps what the new scopes
leave (the dense MLP, norms, the head and the loss).  A ``while`` op's
event spans its body's ops, and the layer scan's loop carries ``grad``
while its body's ops carry the new scopes, so the attribution leaves the
events of loops, conditionals and calls out: each op's time then counts
once, in its own scope.
"""
from __future__ import annotations

import contextlib
import copy
import json
import time
import traceback
from typing import Dict, Optional

from bench import scopes

EP_SCOPES = ("mla", "moe_route", "moe_dispatch", "moe_experts", "moe_shared")
KIND = "train_ep"
CONTAINERS = ("while", "conditional", "call")


@contextlib.contextmanager
def _with_ep_scopes():
    before = scopes.SCOPES
    scopes.SCOPES = before + EP_SCOPES
    try:
        yield
    finally:
        scopes.SCOPES = before


def traffic_for(model: Dict, counts: Dict) -> Optional[Dict]:
    """The ``train_ep`` mix of ``BENCHMARK.json`` whose configuration is
    ``model`` and whose shape is ``counts``; None unless exactly one."""
    bench = json.loads((scopes.ROOT / "BENCHMARK.json").read_text())
    configs = {c["name"]: c for c in bench["configs"]}
    found = []
    for cell in bench["workloads"]:
        p = json.loads((scopes.ROOT / "bench" / "traffic"
                        / f"{cell['traffic']}.json").read_text())
        if p.get("kind") != KIND:
            continue
        cfg = json.loads((scopes.ROOT / configs[cell["config"]]["file"])
                         .read_text())
        if (cfg["model"] == model and p not in found
                and all(p[k] == counts.get(k)
                        for k in scopes.MATCHED_COUNTS)):
            found.append(p)
    return found[0] if len(found) == 1 else None


def compiled_texts(model: Dict, traffic: Dict, devices) -> Dict[str, str]:
    """``scopes.compiled_texts`` for the ``train_ep`` driver's step and
    feed."""
    import jax
    from bench.harness import BENCH, load_module
    from bench.weights import seed_key
    drv = load_module(BENCH / "traffic" / f"{KIND}.py")
    cell = drv.Cell(model, traffic, devices)
    key = seed_key(0, drv.steps.WEIGHTS)
    tables = drv.steps.stream_tables(0, model["vocab_size"], cell.A, traffic)
    state = jax.eval_shape(cell.init, key)
    batch = jax.eval_shape(cell.feed, tables, key, 0)
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        return {scopes.STEP: cell.step.lower(state, batch).compile()
                .as_text(),
                scopes.FEED: cell.feed.lower(tables, key, 0).compile()
                .as_text()}
    finally:
        jax.config.update(flag, before)


def containers(text: str) -> set:
    """Names of the instructions of an HLO text that run other
    computations (:data:`CONTAINERS`)."""
    comps, _ = scopes.parse_hlo(text)
    return {n for ops in comps.values() for n, op in ops.items()
            if op["kind"] in CONTAINERS}


def leaf_ops(red, names: set):
    """A copy of the reduction without the events of ``names``."""
    out = copy.copy(red)
    out.device_ops = {plane: [e for e in ops if e.name not in names]
                      for plane, ops in red.device_ops.items()}
    return out


def read(reading, scope: str) -> Optional[float]:
    """One scope's milliseconds a step in the traced window (any of
    ``scopes.SCOPES`` and :data:`EP_SCOPES`), or None where the run has no
    device trace, is not a ``train_ep`` cell, or its step carries no
    ``scope``.  The first reader of a run compiles the programs, keeps the
    attribution on the ``reading`` and prints it on an earlier line."""
    red = reading.trace
    steps = reading.counts.get("steps_traced")
    if red is None or not red.n_devices or not steps:
        return None
    if not hasattr(reading, "ep_scope_attribution"):
        reading.ep_scope_attribution = _attribution(reading, red, steps)
    result = reading.ep_scope_attribution
    if result is None or scope not in result["present"]:
        return None
    return result["ms"][scope]


def _attribution(reading, red, steps) -> Optional[Dict]:
    t0 = time.perf_counter()
    try:
        traffic = traffic_for(reading.model, reading.counts)
        if traffic is None:
            return None
        import jax
        texts = compiled_texts(reading.model, traffic,
                               jax.devices()[:reading.chips])
        with _with_ep_scopes():
            maps = scopes.program_maps(texts)
            ms = scopes.attribute(leaf_ops(red, containers(
                texts[scopes.STEP])), maps[scopes.STEP], maps[scopes.FEED],
                steps)
    except Exception:  # a reader reports nothing rather than fail the run
        traceback.print_exc()
        print("bench: ep_scopes=null (the attribution failed)", flush=True)
        return None
    busy = red.busy_s() * 1e3 / steps
    info = dict(ms, scopes_found=maps["scopes"], busy_ms_per_step=busy,
                attribution_s=time.perf_counter() - t0)
    print("bench: ep_scopes=" + json.dumps(info), flush=True)
    return {"ms": ms, "present": maps["scopes"]}
