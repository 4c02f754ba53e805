"""The train step names its layers with ``jax.named_scope`` (DESIGN §13):
``bus_unpack``, ``grad``, ``bus_pack``, ``edm_update_bus`` and
``step_metrics``.  The benchmark reads each layer's device time from them
(``bench/scopes.py``), so both step bodies, the synchronous and the
``overlap="delayed"`` one, fused and unfused, are checked here on the
compiled step:

* every dot, and every op whose output has the bus's ``(A, rows, 128)``
  shape, lies under exactly one scope, and every scope is in the HLO;
* the scopes are metadata only: with ``jax.named_scope`` a null context,
  the optimized HLO with its metadata stripped is the same text.
"""
import contextlib
import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ModelConfig, RunConfig
from repro.launch.mesh import gossip_agent_axes, make_gossip_mesh
from repro.models import build_model
from repro.train import build_train_step, init_state, make_gossip_schedule

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import scopes  # noqa: E402

A, SEQ = 2, 16
CASES = [(overlap, fused) for overlap in ("off", "delayed")
         for fused in (False, True)]
IDS = [f"{o}-{'fused' if f else 'unfused'}" for o, f in CASES]
_METADATA = re.compile(r", metadata=\{[^}]*\}")


def _compiled_text(overlap, fused):
    """Optimized HLO of the step as the benchmark builds it: ppermute
    gossip over a one-device mesh holding both agents, packed bus."""
    cfg = ModelConfig(name="scopes-tiny", family="dense", n_layers=2,
                      d_model=64, n_heads=2, n_kv_heads=1, d_ff=128,
                      vocab_size=128)
    model = build_model(cfg)
    run = RunConfig(global_batch=A, seq_len=SEQ, agents="data",
                    algorithm="edm", alpha=0.01, topology="ring",
                    gossip_engine="ppermute", packed_bus=True,
                    agents_per_device=A, overlap=overlap)
    mesh = make_gossip_mesh(A, agents_per_device=A)
    step = jax.jit(build_train_step(model, run, make_gossip_schedule(run, A),
                                    use_fused_kernel=fused, mesh=mesh,
                                    agent_axes=gossip_agent_axes(mesh)),
                   donate_argnums=(0,))
    state = jax.eval_shape(lambda k: init_state(model, run, A, k),
                           jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((A, 1, SEQ), jnp.int32)}
    return step.lower(state, batch).compile().as_text(), state["params"].shape


def _strip(text):
    """The HLO text without metadata: no ``metadata={...}`` and none of
    the module's source tables (file names, stack frames)."""
    keep = [l for l in text.splitlines()
            if l.startswith((" ", "HloModule", "ENTRY", "%", "}"))]
    return "\n".join(_METADATA.sub("", l) for l in keep)


scoped = functools.lru_cache(maxsize=None)(_compiled_text)


@pytest.mark.parametrize("overlap,fused", CASES, ids=IDS)
def test_dots_and_bus_ops_lie_under_one_scope(overlap, fused):
    text, bus_shape = scoped(overlap, fused)
    assert scopes.hlo_scopes(text) == list(scopes.SCOPES)
    comps, _ = scopes.parse_hlo(text)
    ops = scopes.hlo_ops(text)          # the ops the device runs
    bus = re.compile(r"^\w+\[%s\]" % ",".join(map(str, bus_shape)))
    checked = 0
    for c in comps:
        for name, op in comps[c].items():
            if name not in ops or (op["kind"] not in ("dot", "convolution")
                                   and not bus.match(op["shape"])):
                continue
            if op["kind"] in ("parameter", "get-tuple-element", "tuple"):
                continue
            if op["kind"] == "copy" and op["op_name"] is None \
                    and ops[name] is None:
                continue    # XLA's own copy of a donated input: unscoped
            assert len(scopes.scopes_in(op["op_name"])) <= 1, op["line"]
            assert ops[name] in scopes.SCOPES, op["line"]
            checked += 1
    assert checked > 5


@pytest.mark.parametrize("overlap,fused", CASES, ids=IDS)
def test_scopes_change_only_metadata(overlap, fused, monkeypatch):
    text, _ = scoped(overlap, fused)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare, _ = _compiled_text(overlap, fused)
    assert scopes.hlo_scopes(bare) == []
    assert _strip(bare) == _strip(text)
