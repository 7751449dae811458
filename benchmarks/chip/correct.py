"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests due in the window that finished, drawn from the seed and
always holding the longest one and the one with the longest prompt (the
largest prefill bucket reached), is run through the float32 reference
(``reference.py``): each prompt with the tokens served for it.  At every
served token the gap between the reference's best logit and the
reference's logit of the token served is read.  Served tokens are greedy
(the engine takes the argmax), so a sound program serves the reference's
best token, or one within rounding of it, and the gap is 0 or small.

Two numbers of those gaps are held to the configuration's limits
(``correct`` in its file): the widest gap over the sample
(``max_logit_gap``), and their mean over every served token of the
sample (``mean_logit_gap``).  A program whose logits stray further from
the reference flips more of the greedy choices, and by more: the mean
grows with the square of the error and, taken over some thousands of
tokens, is steady from seed to seed, where the widest gap swings.
Besides, every finished request must hold exactly the tokens it asked
for, and every request due in the window must have finished by the end
of the drain (limits 0).

The control takes the program's place: at the same positions of the
same prompts and served tokens, the token that the reference in float8
puts first is read as if it had been served, and the same checks judge
it (``control=True``).
"""

from __future__ import annotations

import numpy as np

from .arrivals import seed_rng
from .reference import Arch, logits_at

__all__ = ["sample", "gaps", "checks"]


def sample(run, seed: int, tokens: int) -> list:
    """Finished window requests: the longest, the widest prompt, and then
    others in an order drawn from the seed, until ``tokens`` served
    tokens are reached."""
    done = [s for s in run.window if s.req is not None and s.req.done]
    if not done:
        return []
    longest = max(done, key=lambda s: len(s.plan.prompt) + len(s.req.output))
    widest = max(done, key=lambda s: len(s.plan.prompt))
    out = [longest] + ([widest] if widest is not longest else [])
    rest = [s for s in done if all(s is not o for o in out)]
    n = sum(len(s.req.output) for s in out)
    for i in seed_rng(seed, 30).permutation(len(rest)):
        if n >= tokens:
            break
        out.append(rest[i])
        n += len(rest[i].req.output)
    return out


def _add(acc: dict, gap: np.ndarray) -> None:
    acc["widest"] = max(acc["widest"], float(gap.max()))
    acc["sum"] += float(gap.sum())
    acc["flips"] += int((gap > 0).sum())


def gaps(params, model: dict, chosen: list, control: bool = False) -> dict:
    """Gaps below the reference's best logit of the served tokens
    (``served``) and, with ``control``, of the tokens that the float8
    reference puts first at the same positions (``control``).  Each has
    the widest gap, their sum and the count of tokens that are not the
    reference's best."""
    a = Arch(model)
    kinds = ("served", "control") if control else ("served",)
    out = {k: {"widest": 0.0, "sum": 0.0, "flips": 0} for k in kinds}
    out["tokens"], out["requests"] = 0, len(chosen)
    for s in chosen:
        served = np.asarray(s.req.output)
        n, m = len(s.plan.prompt), len(served)
        toks = list(s.plan.prompt) + served[:-1].tolist()
        rows = list(range(n - 1, n - 1 + m))
        ref = logits_at(params, toks, rows, a)
        best = ref.max(axis=-1)
        _add(out["served"], best - ref[np.arange(m), served])
        out["tokens"] += m
        if control:
            pick = logits_at(params, toks, rows, a, quant=True).argmax(-1)
            _add(out["control"], best - ref[np.arange(m), pick])
    for k in kinds:
        out[k]["mean"] = out[k]["sum"] / max(1, out["tokens"])
    return out


def checks(run, g: dict, limits: dict, kind: str = "served"
           ) -> list[tuple[str, float, float]]:
    """(name, value, limit) of each number compared; each passes when
    value <= limit.  ``kind`` names whose tokens are judged: the
    program's (``served``) or the control's (``control``)."""
    wrong = sum(1 for s in run.window if s.req is not None and s.req.done
                and len(s.req.output) != s.plan.max_new)
    unfinished = sum(1 for s in run.window
                     if s.req is None or not s.req.done)
    return [("max_logit_gap", g[kind]["widest"],
             float(limits["max_logit_gap"])),
            ("mean_logit_gap", g[kind]["mean"],
             float(limits["mean_logit_gap"])),
            ("wrong_length", float(wrong), 0.0),
            ("unfinished", float(unfinished), 0.0)]
