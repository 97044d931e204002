"""How ``correct`` is decided: served greedy tokens against the plain
reference.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished, drawn from the seed and holding the
longest of them, is run through the plain reference (float32, the
configuration's weights drawn again from their seed) over each prompt
followed by its served tokens. For every served token the gap by which
the reference's logit of that token lies below the reference's best
logit at that position is read. Greedy decoding at bf16 serves a token
within rounding of the best, and only at near-ties; a lower precision,
or a token altered where it is produced, serves tokens below it more
often and by more.

Compared, each with the configuration's limit (``correct`` in its file):
``mean_logit_gap``, the mean of those gaps over the sample's served
tokens, and ``tokens_compared``, the served tokens the sample holds (at
least ``sample_tokens``); every request due in the window has to retire
(an open loop), and every retired request has to carry exactly the
tokens it asked for (EOS is off).

The control (``control="fp8"``) puts the plain reference with weight-only
fp8 in the program's place: at each position of the same prompts and
served tokens, the gap of the token it puts first is read instead.
"""

from __future__ import annotations

import numpy as np

from bench import weights
from bench.spec import Cell, reference_module

REF_BATCH = 4           # sequences per reference call (one weight draw)


def pick_sample(results, seed: int, min_tokens: int):
    """The longest finished request, then others in an order drawn from
    the seed, until the sample holds ``min_tokens`` served tokens."""
    done = sorted((r for r in results if len(r.tokens) > 0),
                  key=lambda r: r.request_id)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.tokens), -r.request_id))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 7])
    sample, n = [longest], len(longest.tokens)
    for i in rng.permutation(len(rest)):
        if n >= min_tokens:
            break
        sample.append(rest[i])
        n += len(rest[i].tokens)
    return sample


def sequence(prompt: np.ndarray, tokens: np.ndarray, t_pad: int,
             n_pad: int) -> tuple[np.ndarray, np.ndarray]:
    """(tokens (t_pad,), positions (n_pad,)): the prompt and all served
    tokens but the last, and the positions whose logits choose each
    served token (padding repeats the last position)."""
    p, n = len(prompt), len(tokens)
    seq = np.zeros(t_pad, np.int32)
    seq[:p] = prompt
    seq[p:p + n - 1] = tokens[:n - 1]
    pos = np.full(n_pad, p + n - 2, np.int32)
    pos[:n] = np.arange(p - 1, p + n - 1)
    return seq, pos


def gaps_of(ref_logits, tokens) -> np.ndarray:
    """Per served token: best reference logit minus the token's."""
    ref = np.asarray(ref_logits)[:len(tokens)]
    tok = np.asarray(tokens, np.int64)
    return ref.max(axis=1) - ref[np.arange(len(tok)), tok]


def served_gaps(cell: Cell, sample, prompts: dict, t_pad: int, n_pad: int,
                control: str = "none") -> dict:
    """{"program": gaps of the served tokens} over the sample, and with
    ``control`` also {"control": at each position, the gap of the token
    the lower-precision reference puts first}."""
    import jax.numpy as jnp
    ref = reference_module(cell.config)
    m = cell.target
    key = weights.model_key(int(cell.config["weights_seed"]), 0)
    out = {"program": []}
    if control != "none":
        out["control"] = []
    for i in range(0, len(sample), REF_BATCH):
        group = sample[i:i + REF_BATCH]
        rows = [sequence(prompts[r.request_id], r.tokens, t_pad, n_pad)
                for r in group]
        rows += [rows[-1]] * (REF_BATCH - len(rows))    # one shape
        seqs = jnp.asarray(np.stack([s for s, _ in rows]))
        pos = jnp.asarray(np.stack([p for _, p in rows]))
        logits = np.asarray(ref.logits_at(m, key, seqs, pos))
        low = None
        if control != "none":
            low = np.asarray(jnp.argmax(
                ref.logits_at(m, key, seqs, pos, control), axis=-1))
        for j, r in enumerate(group):
            out["program"].append(gaps_of(logits[j], r.tokens))
            if low is not None:
                out["control"].append(
                    gaps_of(logits[j], low[j, :len(r.tokens)]))
    return {k: np.concatenate(v) if v else np.zeros(0)
            for k, v in out.items()}


def gap_numbers(gaps: np.ndarray, correct: dict) -> dict:
    """The numbers read from one side's gaps, each beside its limit."""
    return {
        "mean_logit_gap": {"value": float(gaps.mean()) if len(gaps) else
                           0.0, "limit": float(correct["mean_logit_gap"])},
        "tokens_compared": {"value": int(len(gaps)),
                            "limit": int(correct["sample_tokens"])},
    }


def checks(cell: Cell, window, requests, seed: int, t_pad: int,
           n_pad: int, control: str = "none"
           ) -> tuple[dict, int, int, dict]:
    """(numbers compared, each with its limit), attempted, failed, and
    the per-token gaps read (the program's, and the control's with
    ``control``). With ``control`` the control's gaps are the ones
    compared, in the program's place."""
    by_id = {r.request_id: r for r in requests}
    prompts = {r.request_id: r.prompt for r in requests}
    wrong_len = sum(1 for r in window.results
                    if len(r.tokens) != by_id[r.request_id].max_new_tokens)
    if cell.traffic["loop"] == "open":
        attempted = len(requests)
        unfinished = attempted - len(window.results)
    else:
        attempted = len(window.results) + len(window.in_flight)
        unfinished = 0
    correct = cell.config["correct"]
    sample = pick_sample(window.results, seed, int(correct["sample_tokens"]))
    if sample:
        gaps = served_gaps(cell, sample, prompts, t_pad, n_pad, control)
    else:                   # nothing to compare: tokens_compared fails
        gaps = {k: np.zeros(0) for k in ("program", "control")
                if k == "program" or control != "none"}
    out = gap_numbers(gaps["control" if control != "none" else "program"],
                      correct)
    out["unfinished"] = {"value": unfinished, "limit": 0}
    out["wrong_length"] = {"value": wrong_len, "limit": 0}
    return out, attempted, unfinished + wrong_len, gaps


def passed(numbers: dict) -> bool:
    c = numbers
    return (c["mean_logit_gap"]["value"] <= c["mean_logit_gap"]["limit"]
            and c["tokens_compared"]["value"] >= c["tokens_compared"]["limit"]
            and c["unfinished"]["value"] <= c["unfinished"]["limit"]
            and c["wrong_length"]["value"] <= c["wrong_length"]["limit"])
