"""Whether what the window served is correct.

After the window, a sample of the requests it finished, drawn from the
seed, always holding the longest and spread over the slots, is run through
the float32 reference of the configuration's architecture (its module's
``logits``) over each prompt followed by its served tokens.  At every
served position the reference's best logit is compared with its logit of
the token the server chose; the number compared is the widest such gap,
in logits.  Greedy decoding that matched the reference reads about the
rounding of the served arithmetic; a wrong token, position, cache row or
weight reads the spread of the logits themselves.

``controls`` also reads each named control (the architecture's
``CONTROL_DTYPES``): the reference again at a lower precision, taking at
each position of the same prompts and tokens the token that it puts
first.
"""

from __future__ import annotations

import operator

import numpy as np

#: Served tokens the sample holds at least (the longest request counts in
#: full), the distinct slots it covers at least, and the most requests it
#: takes.
SAMPLE_TOKENS = 512
SAMPLE_SLOTS = 4
SAMPLE_REQUESTS = 8

#: How each number compared meets its limit.
RULES = {"logit_gap": operator.le, "payload_dtype": operator.eq,
         "nnz_short": operator.le, "missed": operator.le,
         "slots_checked": operator.ge}


def judge(checks: dict) -> bool:
    """``correct``: every number meets its limit."""
    return all(RULES[name](c["value"], c["limit"])
               for name, c in checks.items())


def sample(finished: dict, results: dict, seed: int) -> list[tuple]:
    """[(slot, prompt, served tokens)] of finished requests (``finished``:
    uid → (slot, prompt)): the longest, then others in an order drawn from
    ``seed``, slots not yet held first, until the sample holds
    ``SAMPLE_TOKENS`` served tokens and ``SAMPLE_SLOTS`` slots, or
    ``SAMPLE_REQUESTS`` requests."""
    uids = sorted(finished, key=lambda u: (-results[u].n_tokens, u))
    if not uids:
        return []
    rest = [uids[1:][i] for i in
            np.random.default_rng([seed, 2]).permutation(len(uids) - 1)]
    first, held = [], {finished[uids[0]][0]}
    for u in rest:                      # one request of each further slot
        if finished[u][0] not in held:
            first.append(u)
            held.add(finished[u][0])
    picked, held = [uids[0]], {finished[uids[0]][0]}
    n = results[uids[0]].n_tokens
    chosen = set(first)
    for u in first + [u for u in rest if u not in chosen]:
        if len(picked) >= SAMPLE_REQUESTS or (
                n >= SAMPLE_TOKENS and len(held) >= SAMPLE_SLOTS):
            break
        picked.append(u)
        held.add(finished[u][0])
        n += results[u].n_tokens
    return [(finished[u][0], np.asarray(finished[u][1]),
             np.asarray(results[u].tokens[: results[u].n_tokens]))
            for u in picked]


def _padded(rows: np.ndarray, bucket: int) -> np.ndarray:
    return np.concatenate([rows, np.full(-len(rows) % bucket, rows[0])])


def gaps(logits, w: dict, samples: list[tuple], dims: dict, max_len: int,
         controls=()) -> dict:
    """Widest gap below the reference's best logit of the served tokens
    (``logit_gap``) and of each control's picks (``controls``: name → gap),
    over every position of ``samples``; the tokens and slots compared.
    ``logits(w, tokens, rows, dims, quant=..., bucket=...)`` is the
    reference.  Every sequence is padded to ``max_len``, so that one
    compiled reference serves them all."""
    out = {"logit_gap": 0.0, "tokens": 0,
           "slots": len({slot for slot, _, _ in samples}),
           "controls": {name: 0.0 for name in controls}}
    for _, prompt, toks in samples:
        n, plen = len(toks), len(prompt)
        seq = np.concatenate([prompt, toks[:-1]]).astype(np.int32)
        rows = _padded(np.arange(plen - 1, plen - 1 + n), max_len)
        ref = np.asarray(logits(w, seq, rows, dims, bucket=max_len))[:n]
        best = ref.max(-1)
        out["logit_gap"] = max(out["logit_gap"], float(
            (best - ref[np.arange(n), toks]).max()))
        out["tokens"] += n
        for name in controls:
            ctl = np.asarray(logits(w, seq, rows, dims, quant=name,
                                    bucket=max_len))[:n]
            pick = ctl.argmax(-1)
            out["controls"][name] = max(out["controls"][name], float(
                (best - ref[np.arange(n), pick]).max()))
    return out
