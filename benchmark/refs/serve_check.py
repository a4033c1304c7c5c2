"""The check of a served model: one reference forward over each sampled
request's prompt plus the tokens it was served, and at every generated
position the gap by which the served token's reference logit lies below
the reference's best. Valid for greedy tokens only."""

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from . import layerwise as lw

PAD_TO = 256      # sequence lengths are padded up to a multiple of this
ROWS = 128        # and the positions read, to a multiple of this


_HEADS = {}


def _head(head_logits):
    """One jitted head a model: a new ``jax.jit`` a call would lower the
    50,304-wide projection anew for every request."""
    if head_logits not in _HEADS:
        _HEADS[head_logits] = jax.jit(head_logits)
    return _HEADS[head_logits]


def _logits(trainer: lw.Layerwise, head_logits, params, tokens: List[int],
            first: int):
    """Reference logits at positions first-1 .. len(tokens)-2: those that
    predict tokens[first:]. Right padding cannot reach them (causal)."""
    T = len(tokens)
    padded = -(-(T + 1) // PAD_TO) * PAD_TO
    ids = np.zeros((1, padded + 1), np.int32)   # embed drops the last id
    ids[0, :T] = tokens
    x = trainer.forward(params, trainer.place_rows((ids,)))
    outer = {k: v for k, v in params.items() if k != "layers"}
    n = T - first
    rows = np.minimum(np.arange(first - 1, first - 1 - (-n // ROWS) * ROWS), T - 2)
    return _head(head_logits)(outer, x[:, jnp.asarray(rows)])[0, :n]


def served_gaps(model, trainer, params, requests: List[Dict],
                control_model=None, control_trainer=None) -> Dict:
    """requests: [{"prompt": [...], "output": [...]}]. Returns the widest
    gap of the served tokens and, with a control model (the reference in
    a lower precision, put in the program's place), of the tokens that
    the control puts first."""
    widest, control_widest, n = 0.0, 0.0, 0
    for r in requests:
        toks = list(r["prompt"]) + list(r["output"])
        ref = _logits(trainer, model[3], params, toks, len(r["prompt"]))
        best = jnp.max(ref, axis=-1)
        served = jnp.asarray(r["output"], jnp.int32)
        gaps = best - jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
        widest = max(widest, float(jnp.max(gaps)))
        n += len(r["output"])
        if control_model is not None:
            low = _logits(control_trainer, control_model[3], params, toks,
                          len(r["prompt"]))
            first = jnp.argmax(low, axis=-1)
            cg = best - jnp.take_along_axis(ref, first[:, None], axis=-1)[:, 0]
            control_widest = max(control_widest, float(jnp.max(cg)))
    out = {"widest_gap": widest, "tokens": n}
    if control_model is not None:
        out["control_widest_gap"] = control_widest
    return out
