"""Device time of the train step's layers, read through the program's own
scopes.

The train step (``repro.train.trainer.build_train_step``) wraps each of its
layers in a ``jax.named_scope`` (:data:`SCOPES`).  A scope is metadata: it
lands in the ``op_name`` of every HLO instruction traced under it, and the
compiled step's HLO text keeps it on the ops the device runs.  A TPU trace
names an op event by its instruction (``fusion.61``), so the attribution
reads the compiled HLO of the programs the traced window ran beside the
trace:

- :func:`hlo_ops` parses an optimized HLO text into {instruction: scope or
  None} for the instructions the device runs as ops.  An op takes the scope
  of its own ``op_name``; a fusion without one, the scope of its fused ops;
  an op in a loop body without one, the loop's; an op XLA made (a copy, a
  hoisted convert, a concatenate turned into dynamic-update-slices), the
  scope of the ops it reads, else of the ops that read it;
- :func:`label_programs` splits a chip's ops between the step and the
  feed.  Instruction names repeat across programs; a program runs its ops
  one after another, so an op whose name both programs have goes with the
  nearest op whose name only one of them has;
- :func:`attribute` gives, per scope, the union of its ops' intervals on
  each chip inside the traced window (a ``while`` encloses its body's ops,
  so durations are not summed), averaged over the chips, per step.

:func:`read` is what the per-layer readers ``bench/metrics/<scope>_ms.py``
call.  The harness hands the readers the trace and the run's counts, not
its compiled step, so :func:`read` finds the cell's mix from those counts
and compiles the step and the feed again, outside the traced window.
"""
from __future__ import annotations

import collections
import gzip
import json
import pathlib
import re
import time
import traceback
from typing import Dict, Iterable, List, Optional, Tuple

from bench import trace as btrace

ROOT = pathlib.Path(__file__).resolve().parent.parent
SCOPES = ("bus_unpack", "grad", "bus_pack", "edm_update_bus", "step_metrics")
STEP, FEED = "step", "feed"
MATCHED_COUNTS = ("agents", "agents_per_device", "per_agent_batch", "seq_len")

_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_RUN_CALLS = re.compile(r"\b(?:body|condition|true_computation|"
                        r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_TO_APPLY = re.compile(r"\bto_apply=%?([\w.\-]+)")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_SOURCES = ("parameter", "constant", "iota")
_WRAPPER = re.compile(r"^(\w+)\((.*)\)$")


def scopes_in(op_name: Optional[str]) -> List[str]:
    """The :data:`SCOPES` among the path components of ``op_name``, a
    component bare or under transform wrappers (``vmap(grad)``,
    ``transpose(jvp(grad))``); ``jit(<function>)`` names a function, not a
    scope."""
    found = []
    for comp in (op_name or "").split("/"):
        m = _WRAPPER.match(comp)
        while m and m.group(1) not in ("jit", "pjit"):
            comp = m.group(2)
            m = _WRAPPER.match(comp)
        if comp in SCOPES and comp not in found:
            found.append(comp)
    return found


def parse_hlo(text: str) -> Tuple[Dict[str, Dict[str, dict]], str]:
    """({computation: {instruction: {kind, shape, op_name, line}}}, the
    entry computation's name)."""
    comps: Dict[str, Dict[str, dict]] = {}
    entry, cur = None, None
    for line in text.splitlines():
        if not line.startswith((" ", "\t")):
            m = _COMPUTATION.match(line)
            if m:
                cur = comps.setdefault(m.group(2), {})
                if m.group(1):
                    entry = m.group(2)
            continue
        m = _INSTRUCTION.match(line)
        if m and cur is not None:
            name = _OP_NAME.search(line)
            cur[m.group(1)] = dict(kind=m.group(3), shape=m.group(2),
                                   op_name=name.group(1) if name else None,
                                   operands=_operands(line, m.end()),
                                   line=line)
    if entry is None:
        raise ValueError("no ENTRY computation in the HLO text")
    return comps, entry


def _operands(line: str, start: int) -> List[str]:
    """Names of the instructions inside the parentheses that open at
    ``start - 1``: the op's operands."""
    depth, end = 1, start
    while end < len(line) and depth:
        depth += {"(": 1, ")": -1}.get(line[end], 0)
        end += 1
    return _OPERAND.findall(line[start:end])


def scope_of(op: dict, comps: Dict[str, Dict[str, dict]],
             outer: Optional[str]) -> Optional[str]:
    """The innermost scope in the op's ``op_name``.  A fusion without one
    (XLA names a fusion after its root, which may be an op XLA made) takes
    the scope most of its fused ops carry; any other op without one takes
    the scope of the loop, conditional or call that runs it (``outer``)."""
    found = scopes_in(op["op_name"])
    if found:
        return found[-1]
    m = _CALLS.search(op["line"]) if op["kind"] == "fusion" else None
    if m and m.group(1) in comps:
        inner = collections.Counter(
            s for o in comps[m.group(1)].values()
            for s in scopes_in(o["op_name"])[-1:])
        if inner:
            return inner.most_common(1)[0][0]
    return outer


def hlo_ops(text: str) -> Dict[str, Optional[str]]:
    """{instruction the device runs: its scope, or None}: the ops of the
    entry computation and of what its loops, conditionals and calls run,
    not of the bodies of fusions or reducers.  An op that is left without
    a scope (one XLA made: a copy, a convert it hoisted, the
    dynamic-update-slices it made of a concatenate, a loop it split off)
    takes the scope of the ops it reads, once they all have the same one;
    else the one scope of the ops that read it."""
    comps, entry = parse_hlo(text)
    out: Dict[str, Optional[str]] = {}
    todo: List[Tuple[str, Optional[str]]] = [(entry, None)]
    seen = set()
    while todo:
        c, outer = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        ops = comps[c]
        scope = {n: scope_of(op, comps, outer) for n, op in ops.items()}
        reads = {n: [a for a in op["operands"]
                     if a in ops and ops[a]["kind"] not in _SOURCES]
                 for n, op in ops.items()}
        read_by: Dict[str, List[str]] = {n: [] for n in ops}
        for n, args in reads.items():
            for a in args:
                read_by[a].append(n)
        while True:
            while _take(scope, reads, every=True):
                pass
            if not _take(scope, read_by, every=False):
                break
        out.update(scope)
        for n, op in ops.items():
            line = op["line"]
            called = _RUN_CALLS.findall(line)
            for group in _BRANCHES.findall(line):
                called += [b.strip().lstrip("%") for b in group.split(",")]
            if op["kind"] == "call":
                called += _TO_APPLY.findall(line)
            todo += [(callee, scope[n]) for callee in called]
    return out


def _take(scope: Dict[str, Optional[str]], links: Dict[str, List[str]],
          every: bool) -> bool:
    """Give each op without a scope the one scope of its ``links`` (of
    ``every`` one of them, or of those that have one); whether any op took
    one."""
    new = {}
    for n, s in scope.items():
        found = [scope[a] for a in links[n]]
        if s is None and found and (not every or None not in found):
            found = set(found) - {None}
            if len(found) == 1:
                new[n] = found.pop()
    scope.update(new)
    return bool(new)


def hlo_scopes(text: str) -> List[str]:
    """The :data:`SCOPES` that some instruction of the HLO text carries,
    fused or not."""
    found = {s for m in _OP_NAME.finditer(text) for s in scopes_in(m.group(1))}
    return [s for s in SCOPES if s in found]


# ---------------------------------------------------------------------------
# events → programs → scopes
# ---------------------------------------------------------------------------

def label_programs(ops: List[btrace.Event], step: Iterable[str],
                   feed: Iterable[str]) -> List[Optional[str]]:
    """STEP, FEED or None (in neither program) for each of one chip's ops,
    in the order given.  An op whose name both programs have takes the
    label of the nearest op, by the gap between their intervals, whose name
    only one has (the previous one on a tie)."""
    step, feed = set(step), set(feed)
    own = [STEP if e.name in step and e.name not in feed else
           FEED if e.name in feed and e.name not in step else
           ("both" if e.name in step else None) for e in ops]
    order = sorted(range(len(ops)), key=lambda i: ops[i].start_ns)
    prev: Dict[int, int] = {}
    last = None
    for i in order:
        if own[i] == "both":
            prev[i] = last
        elif own[i] is not None:
            last = i
    labels = list(own)
    nxt = None
    for i in reversed(order):
        if own[i] in (STEP, FEED):
            nxt = i
            continue
        if own[i] != "both":
            continue
        p, e = prev[i], ops[i]
        gap_p = (max(0.0, e.start_ns - ops[p].end_ns) if p is not None
                 else float("inf"))
        gap_n = (max(0.0, ops[nxt].start_ns - e.end_ns) if nxt is not None
                 else float("inf"))
        if p is None and nxt is None:
            labels[i] = STEP
        else:
            labels[i] = own[p] if gap_p <= gap_n else own[nxt]
    return labels


def attribute(red: btrace.Reduction, step_ops: Dict[str, Optional[str]],
              feed_names: Iterable[str], steps: int) -> Dict:
    """Milliseconds a step, averaged over the chips, inside the traced
    window: each scope's ops, the step's ops in no scope (``unscoped``,
    with its five largest instructions by summed time), the step program's busy
    time (``step``), the feed program's (``feed``) and ops found in
    neither (``other``)."""
    feed_names = set(feed_names)
    keys = SCOPES + ("unscoped", STEP, FEED, "other")
    acc = dict.fromkeys(keys, 0.0)
    unscoped_by_name: Dict[str, float] = {}
    for ops in red.device_ops.values():
        labels = label_programs(ops, step_ops, feed_names)
        groups: Dict[str, list] = {k: [] for k in keys}
        for e, lab in zip(ops, labels):
            iv = (e.start_ns, e.end_ns)
            if lab is None:
                groups["other"].append(iv)
            elif lab == FEED:
                groups[FEED].append(iv)
            else:
                groups[STEP].append(iv)
                scope = step_ops[e.name] or "unscoped"
                groups[scope].append(iv)
                if scope == "unscoped":
                    key = re.sub(r"\.\d+$", "", e.name)
                    lo, hi = max(e.start_ns, red.lo), min(e.end_ns, red.hi)
                    unscoped_by_name[key] = (unscoped_by_name.get(key, 0.0)
                                             + max(0.0, hi - lo))
        for k, ivs in groups.items():
            acc[k] += btrace.length(btrace.clip(btrace.union(ivs),
                                                red.lo, red.hi))
    per = 1e-6 / max(red.n_devices, 1) / max(steps, 1)
    out = {k: v * per for k, v in acc.items()}
    out["unscoped_top"] = [
        [k, v * per] for k, v in sorted(unscoped_by_name.items(),
                                        key=lambda kv: -kv[1])[:5]]
    return out


# ---------------------------------------------------------------------------
# the compiled programs of a cell
# ---------------------------------------------------------------------------

def traffic_for(model: Dict, counts: Dict) -> Optional[Dict]:
    """The training mix of ``BENCHMARK.json`` whose configuration is
    ``model`` and whose shape is ``counts``; None unless exactly one."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    configs = {c["name"]: c for c in bench["configs"]}
    found = []
    for cell in bench["workloads"]:
        cfg = json.loads((ROOT / configs[cell["config"]]["file"])
                         .read_text())
        p = json.loads((ROOT / "bench" / "traffic"
                        / f"{cell['traffic']}.json").read_text())
        if (p.get("kind") == "train_steps" and cfg["model"] == model
                and all(p[k] == counts.get(k) for k in MATCHED_COUNTS)
                and p not in found):
            found.append(p)
    return found[0] if len(found) == 1 else None


def compiled_texts(model: Dict, traffic: Dict, devices) -> Dict[str, str]:
    """Optimized HLO texts of the step and the feed that the traffic kind
    ``train_steps`` runs for ``model`` and ``traffic``, compiled from shapes
    alone.
    JAX's persistent cache leaves metadata out of its key by default, so a
    cached step may carry another build's ``op_name``s; these compiles put
    the metadata into the key."""
    import jax
    from bench.harness import BENCH, load_module
    from bench.weights import seed_key
    drv = load_module(BENCH / "traffic" / "train_steps.py")
    cell = drv.Cell(model, traffic, devices)
    key = seed_key(0, drv.WEIGHTS)
    tables = drv.stream_tables(0, model["vocab_size"], cell.A, traffic)
    state = jax.eval_shape(cell.init, key)
    batch = jax.eval_shape(cell.feed, tables, key, 0)
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        return {STEP: cell.step.lower(state, batch).compile().as_text(),
                FEED: cell.feed.lower(tables, key, 0).compile().as_text()}
    finally:
        jax.config.update(flag, before)


def program_maps(texts: Dict[str, str]) -> Dict:
    """What the attribution needs of the compiled programs: the step's ops
    with their scopes, the scopes the step carries at all (a scope whose
    ops were all fused into another's owns no op), the feed's ops."""
    return {STEP: hlo_ops(texts[STEP]), "scopes": hlo_scopes(texts[STEP]),
            FEED: sorted(hlo_ops(texts[FEED]))}


def save_maps(maps: Dict, path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(maps, f)


def read_maps(path) -> Dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def read(reading, scope: str) -> Optional[float]:
    """One scope's milliseconds a step in the traced window, or None where
    the run has no device trace, the mix cannot be told from the counts,
    or the step carries no ``scope`` (a program without the scopes).  The
    first reader of a run compiles the programs, keeps the attribution on
    the ``reading`` every reader of the run shares and prints it on an
    earlier line."""
    red = reading.trace
    steps = reading.counts.get("steps_traced")
    if red is None or not red.n_devices or not steps:
        return None
    if not hasattr(reading, "scope_attribution"):
        reading.scope_attribution = _attribution(reading, red, steps)
    result = reading.scope_attribution
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
        maps = program_maps(compiled_texts(reading.model, traffic,
                                           jax.devices()[:reading.chips]))
    except Exception:  # a reader reports nothing rather than fail the run
        traceback.print_exc()
        print("bench: scopes=null (the attribution failed)", flush=True)
        return None
    ms = attribute(red, maps[STEP], maps[FEED], steps)
    info = dict(ms, scopes_found=maps["scopes"],
                attribution_s=time.perf_counter() - t0)
    print("bench: scopes=" + json.dumps(info), flush=True)
    return {"ms": ms, "present": maps["scopes"]}
