"""Plain float32 reference of served tokens: the full forward pass over a
request's prompt and its served tokens, layer by layer from the weights as
served (each layer upcast to float32 when it is used), so a model the size
of one chip fits beside nothing else.

The check on a served request is the gap by which each served token's
logit lies below the reference's best logit at the same position; it is
valid for greedy decoding.  The control is judged the same way, at the
same served positions: the token a lower-precision forward pass puts
first there, in the program's place.
"""
from __future__ import annotations

import functools
import json
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import lm

PAD_TO = 512
ROWS_TO = 256


@functools.lru_cache(maxsize=None)
def _fns(m_json: str, dtype, q_block: int):
    m = json.loads(m_json)
    eps = m.get("norm_eps", 1e-6)
    dt = None if dtype is None else jnp.dtype(dtype)

    @jax.jit
    def embed(table, tokens):
        return jnp.take(table, tokens, axis=0).astype(jnp.float32)

    @jax.jit
    def layer(lp, x):
        lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
        with jax.default_matmul_precision("highest"):
            return lm.layer(lp, m, x, jnp.arange(x.shape[1]), dt, q_block)

    @jax.jit
    def logits(final_ln, head, x):
        with jax.default_matmul_precision("highest"):
            h = lm.rms_norm(x, final_ln.astype(jnp.float32), eps)
            return lm.mm(h, head.astype(jnp.float32), dt)

    return embed, layer, logits


def _padded(a: np.ndarray, to: int) -> np.ndarray:
    """``a`` with zeros at its end up to a multiple of ``to``."""
    out = np.zeros(-(-len(a) // to) * to, a.dtype)
    out[:len(a)] = a
    return out


def forward_logits(params, m: Dict, tokens: np.ndarray, rows: np.ndarray,
                   dtype=None) -> jax.Array:
    """Logits at positions ``rows`` of the token sequence, padded to a
    multiple of 256 rows (the padding rows repeat position 0).  The
    sequence is padded at its end to a multiple of 512 tokens (causal
    attention keeps padding out of earlier positions), so a few shapes
    serve every request."""
    embed, layer, logits = _fns(json.dumps(m, sort_keys=True), dtype,
                                PAD_TO)
    x = embed(params["embed"], jnp.asarray(_padded(
        np.asarray(tokens, np.int32), PAD_TO))[None])
    (blocks,) = params["blocks"]
    for i in range(m["n_layers"]):
        x = layer(jax.tree.map(lambda a: a[i], blocks), x)
    h = x[0, jnp.asarray(_padded(np.asarray(rows, np.int32), ROWS_TO))][None]
    return logits(params["final_ln"], params["lm_head"], h)[0]


@jax.jit
def _gaps(ref, pick):
    """Per row, the gap between the best logit of ``ref`` and that of the
    token ``pick`` names."""
    return jnp.max(ref, -1) - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]


def _positions(req, served):
    """(tokens fed, rows whose next token was served)."""
    seq = np.concatenate([req.tokens, np.asarray(served[:-1], np.int32)])
    S = len(req.tokens)
    return seq, np.arange(S - 1, S - 1 + len(served))


def served_gaps(params, m: Dict, reqs: Sequence, completed: Dict,
                dtype=None) -> np.ndarray:
    """The gap at every served position of ``reqs`` between the
    reference's best logit and the served token's; with ``dtype``, the
    token that a ``dtype`` forward pass over the same tokens puts first
    there takes the served token's place (the control)."""
    out = [np.zeros(0, np.float32)]
    for r in reqs:
        served = np.asarray(completed[r.rid], np.int32)
        seq, rows = _positions(r, served)
        ref = forward_logits(params, m, seq, rows)
        if dtype is None:
            pick = jnp.asarray(_padded(served, ROWS_TO))
        else:
            pick = jnp.argmax(forward_logits(params, m, seq, rows, dtype), -1)
        out.append(np.asarray(_gaps(ref, pick))[:len(rows)])
        del ref, pick
    return np.concatenate(out)


def widest_gap(params, m: Dict, reqs: Sequence, completed: Dict) -> float:
    """The widest of :func:`served_gaps`."""
    return float(np.max(served_gaps(params, m, reqs, completed),
                        initial=0.0))
