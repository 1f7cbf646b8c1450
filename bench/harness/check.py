"""What decides ``correct``: served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample
drawn from the seed of the requests the window finished, the longest
among them, is run through the reference: one full forward over each
prompt and its served tokens.  At each served position the number read
is the gap by which the served token's logit lies below the
reference's best there.  Greedy serving in bfloat16 lands within a
small gap of the float32 reference; the run's number is the widest gap
over every sampled token.

The control is the reference in float8 put in the program's place: at
the same positions of the same tokens, the token it puts first is
scored as if the program had served it, by the same ``verdict``.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def sample(reqs, n: int, seed: int) -> List[Any]:
    """``n`` of the requests that finished ok: the one with the longest
    prompt and output, and the rest drawn from ``seed``."""
    done = [r for r in reqs if r.ok]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + len(r.res.tokens),
                                       r.due))
    others = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), 7])
    k = min(n - 1, len(others))
    picks = rng.choice(len(others), k, replace=False) if k else []
    return [longest] + [others[i] for i in sorted(picks)]


@jax.jit
def _gap_at(ref, tokens):
    """max(ref) - ref[tokens] per row."""
    pick = jnp.take_along_axis(ref, tokens[:, None], axis=1)[:, 0]
    return jnp.max(ref, axis=1) - pick


def compare(reference, params, config, reqs, pad_len: int,
            control: bool = False) -> Dict[str, float]:
    """Widest gap of the served tokens of ``reqs`` (and, with
    ``control``, of the float8 reference's tokens) below the
    reference's best."""
    served_gap, control_gap, n_tok = 0.0, 0.0, 0
    for r in reqs:
        prompt = np.asarray(r.res.prompt, np.int32)
        out = np.asarray(r.res.tokens, np.int32)
        seq = np.zeros((pad_len,), np.int32)
        seq[:len(prompt)] = prompt
        seq[len(prompt):len(prompt) + len(out) - 1] = out[:-1]
        start, n = len(prompt) - 1, len(out)
        ref = reference.logits(params, config, seq, start, n)
        served_gap = max(served_gap,
                         float(jnp.max(_gap_at(ref, jnp.asarray(out)))))
        if control:
            ctl = reference.logits(params, config, seq, start, n, "fp8")
            first = jnp.argmax(ctl, axis=1).astype(jnp.int32)
            control_gap = max(control_gap,
                              float(jnp.max(_gap_at(ref, first))))
        n_tok += n
    out = {"served_gap": served_gap, "n_tokens": n_tok}
    if control:
        out["control_gap"] = control_gap
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """``correct`` when every number compared is at or below its limit;
    and each number beside its limit."""
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
