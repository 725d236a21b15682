"""The one traffic generator: reads ``bench/traffic/<name>.json``.

A mix is a list of client groups.  Each group has a client count, a total
arrival rate, Zipf shares that split its arrivals over its clients, an
optional backlog of requests per client due at the window's start, and a
length model:

- ``"lmsys"``: LMSYS-Chat-like prompt and output lengths, copied from the
  program's ``repro.workloads.traces`` (``sample_prompt``,
  ``true_output_len``) so that a change to the program cannot move the
  yardstick;
- ``"uniform"``: prompts and outputs uniform in the given ranges.

Arrivals are a Poisson process conditioned on its count: a window of
``seconds`` holds ``round(rate * seconds)`` arrivals of the group at
uniform random times, so every window offers the same number of requests.

Prompts are clipped to ``prompt_max`` and outputs so that prompt plus
output stays within ``total_max``.

The mix's ``shape_seed`` fixes the set of requests: their clients,
arrival times, lengths and keywords.  The run's ``--seed`` draws the
prompt token ids (and, elsewhere, the weights): every seed offers the
same amount and order of work, on different inputs.  Greedy decoding
runs each request to its ``output_len`` whatever its tokens, so the
schedule of work does not depend on the seed.
"""
from __future__ import annotations

import numpy as np

from config import BENCH, load_json

# copied from repro.workloads.traces: intent -> (base output length,
# prompt-length exponent, noise sigma), the LMSYS-ish intent mix, filler
INTENTS = {
    "qa":        (26.0, 0.10, 0.45),
    "chat":      (100.0, 0.15, 0.55),
    "summarize": (60.0, 0.55, 0.40),
    "translate": (55.0, 0.90, 0.25),
    "code":      (360.0, 0.25, 0.60),
    "story":     (800.0, 0.10, 0.50),
}
INTENT_NAMES = tuple(INTENTS)
INTENT_PROBS = np.array([0.20, 0.28, 0.11, 0.07, 0.19, 0.15])
FILLER = ("the", "a", "of", "to", "in", "and", "for", "with", "on", "is",
          "how", "what", "why", "when", "best", "new", "my", "your")


def true_output_len(intent: str, prompt_len: int, rng) -> int:
    base, gamma, sigma = INTENTS[intent]
    mean = base * (prompt_len / 128.0) ** gamma
    return int(np.clip(mean * rng.lognormal(0.0, sigma), 1, 4096))


def sample_prompt(rng):
    """(keywords, prompt_len, intent)."""
    intent = str(rng.choice(INTENT_NAMES, p=INTENT_PROBS))
    prompt_len = int(np.clip(rng.lognormal(4.45, 0.95), 4, 3500))
    n_fill = int(rng.integers(2, 6))
    return (intent,) + tuple(rng.choice(FILLER, size=n_fill)), prompt_len, \
        intent


def zipf_shares(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1) ** s
    return w / w.sum()


def _lengths(group: dict, rng):
    if group["lengths"] == "lmsys":
        kw, plen, intent = sample_prompt(rng)
        out = true_output_len(intent, plen, rng)
    else:
        plen = int(rng.integers(*group["prompt"], endpoint=True))
        out = int(rng.integers(*group["output"], endpoint=True))
        kw = tuple(group.get("keywords", ("summarize",)))
    plen = min(plen, group.get("prompt_max", plen))
    out = max(1, min(out, group["total_max"] - plen))
    return kw, plen, out


def load_mix(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def generate(mix: dict, seed: int, seconds: float, vocab: int):
    """The requests due in ``[0, seconds)``, sorted by due time: dicts with
    ``client``, ``group``, ``due``, ``prompt_len``, ``output_len``,
    ``keywords``, ``tokens`` and the group's ``ttft`` and ``fair`` flags."""
    shape = np.random.default_rng(mix["shape_seed"])
    values = np.random.default_rng([int(seed), 1])
    reqs = []
    for group in mix["groups"]:
        names = [f"{group['name']}{i}" for i in range(group["clients"])]
        shares = zipf_shares(group["clients"], group.get("zipf", 0.0))
        n = int(round(group["rate_per_s"] * seconds))
        arrivals = [(1e-3 * j, ci) for ci in range(len(names))
                    for j in range(group.get("backlog", 0))]
        arrivals += zip(np.sort(shape.uniform(0.0, seconds, n)),
                        shape.choice(len(names), size=n, p=shares))
        for due, ci in arrivals:
            kw, plen, out = _lengths(group, shape)
            reqs.append(dict(client=names[ci], group=group["name"],
                             due=float(due), prompt_len=plen,
                             output_len=out, keywords=kw,
                             ttft=group["ttft"],
                             fair=group.get("fair", False)))
    reqs.sort(key=lambda r: (r["due"], r["client"]))
    for r in reqs:
        r["tokens"] = values.integers(0, vocab, r["prompt_len"],
                                     dtype=np.int32)
    return reqs
