"""Whether what the timed path served is right: the comparison with the
plain reference that decides ``correct``.

For each sampled request, the reference runs once over its prompt and
its served tokens (teacher-forced).  At each served position the gap is
how far the served token's reference logit lies below the reference's
best; greedy serving in the configured precision keeps it near zero.
The control reads, at the same positions, the gap of the token that the
reference computed in a lower precision puts first.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class Served:
    rid: str
    prompt: np.ndarray          # int32 ids
    tokens: np.ndarray          # served ids, in order
    compare_from: int = 0       # index of the first served token compared


def positions(s: Served) -> Tuple[np.ndarray, np.ndarray]:
    """(input sequence, rows whose logits predict the compared tokens)."""
    seq = np.concatenate([s.prompt, s.tokens[:-1]]).astype(np.int32)
    rows = np.arange(len(s.prompt) - 1 + s.compare_from,
                     len(s.prompt) - 1 + len(s.tokens))
    return seq, rows


def gaps(ref_logits, picked) -> np.ndarray:
    """max(reference) - reference[picked], per row."""
    best = jnp.max(ref_logits, -1)
    got = jnp.take_along_axis(ref_logits, jnp.asarray(picked)[:, None],
                              -1)[:, 0]
    return np.asarray(best - got, np.float64)


def served_and_control_gaps(ref, cfg: Dict, params: Dict,
                            sample: Sequence[Served], control: bool
                            ) -> Tuple[np.ndarray, np.ndarray]:
    """Gaps of the served tokens and, with ``control``, of the tokens the
    fp8 reference puts first at the same positions."""
    served: List[np.ndarray] = []
    ctrl: List[np.ndarray] = []
    for s in sample:
        seq, rows = positions(s)
        lg = ref.logits_at(cfg, params, seq, rows)
        served.append(gaps(lg, s.tokens[s.compare_from:]))
        if control:
            lq = ref.logits_at(cfg, params, seq, rows, quant="fp8")
            ctrl.append(gaps(lg, np.asarray(jnp.argmax(lq, -1))))
            del lq
        del lg
    cat = (lambda xs: np.concatenate(xs) if xs else np.zeros(0))
    return cat(served), cat(ctrl)


def readings(g: np.ndarray) -> Dict[str, float]:
    return {"max_logit_gap": float(g.max()), "mean_logit_gap": float(g.mean())}


def judge(read: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Every limited reading at or under its limit (and finite)."""
    checks = {k: {"value": read[k], "limit": float(v)}
              for k, v in limits.items()}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
