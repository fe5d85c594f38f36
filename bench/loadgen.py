"""One general load generator: it reads a traffic file (``bench/traffic/
<mix>.json``) and turns it into the requests of one run.

Every seed gives the same work. The sizes of the requests (prompt,
shared head or document, question and output lengths, trust tier), the
gaps between arrivals and their order are drawn once from the traffic
file's ``shape_seed``; ``--seed`` writes the text (and the run draws the
weights from it). Near a cell's knee the order decides which requests
meet in the queue: with the order permuted per seed, the tail latency of
one window spread by ~40% between seeds against under 1% between two runs
of one seed (qwen3-4b.chat-shared on a TPU v5e), so the order is fixed.

Lengths are in byte-tokenizer tokens (one ASCII character is one token,
plus one begin token per prompt). The sampling primitives (bounded Pareto,
Zipf ranks, discrete mixtures) follow ``repro.core.tracegen``; the text
of the RAG questions follows the healthcare templates of
``repro.core.workload``, so that MIST finds typed PII in them. Both are
copied here so that the yardstick cannot move with the program.
"""
from __future__ import annotations

import json
import math
import random
from bisect import bisect_left
from dataclasses import dataclass
from pathlib import Path

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"

# ------------------------------------------------------------ primitives


def bounded_pareto_int(rng: random.Random, alpha: float, lo: int,
                       hi: int) -> int:
    """Heavy-tailed integer in [lo, hi]: a Pareto(alpha) tail off lo,
    truncated at hi. One uniform draw."""
    u = 1.0 - rng.random()
    return min(hi, max(lo, int(lo / u ** (1.0 / alpha))))


def mixture_index(rng: random.Random, weights) -> int:
    u = rng.random()
    total = float(sum(weights)) or 1.0
    acc = 0.0
    for i, w in enumerate(weights):
        acc += w / total
        if u < acc:
            return i
    return len(weights) - 1


class Zipf:
    """Zipf(s) over n ranks; rank 0 is the most popular."""

    def __init__(self, n: int, s: float):
        w = [1.0 / (i + 1) ** s for i in range(n)]
        tot, acc, self.cdf = sum(w), 0.0, []
        for x in w:
            acc += x / tot
            self.cdf.append(acc)
        self.cdf[-1] = 1.0

    def sample(self, rng: random.Random) -> int:
        return bisect_left(self.cdf, rng.random())


# ------------------------------------------------------------ text

_WORDS = ("the", "of", "and", "a", "to", "in", "is", "that", "for", "it",
          "with", "as", "was", "on", "be", "at", "by", "this", "had", "not",
          "are", "but", "from", "or", "have", "an", "they", "which", "one",
          "you", "were", "all", "we", "when", "your", "can", "said", "there",
          "use", "each", "how", "their", "will", "other", "about", "out",
          "many", "then", "them", "these", "some", "would", "make", "like",
          "into", "time", "look", "more", "write", "see", "number", "way",
          "could", "people", "than", "first", "water", "been", "call", "who",
          "its", "now", "find", "long", "down", "day", "did", "get", "come",
          "made", "may", "part", "over", "new", "sound", "take", "only",
          "little", "work", "know", "place", "year", "live", "back", "give",
          "most", "very", "after", "thing", "our", "just", "name", "good",
          "sentence", "man", "think", "say", "great", "where", "help",
          "through", "much", "before", "line", "right", "too", "mean", "old",
          "any", "same", "tell", "boy", "follow", "came", "want", "show",
          "also", "around", "form", "three", "small", "set", "put", "end",
          "does", "another", "well", "large", "must", "big", "even", "such",
          "because", "turn", "here", "why", "ask", "went", "men", "read",
          "need", "land", "different", "home", "us", "move", "try", "kind",
          "hand", "picture", "again", "change", "off", "play", "spell", "air",
          "away", "animal", "house", "point", "page", "letter", "mother",
          "answer", "found", "study", "still", "learn", "should", "world")


def words(rng: random.Random, n_chars: int) -> str:
    """Lower-case prose of exactly ``n_chars`` characters. No capitals,
    digits or punctuation, so no MIST pattern can match inside it and a
    shared head or document survives sanitization unchanged."""
    out, size = [], 0
    while size < n_chars:
        w = rng.choice(_WORDS)
        out.append(w)
        size += len(w) + 1
    return " ".join(out)[:n_chars].ljust(n_chars, " ")


# copied from repro.core.workload: PHI-bearing clinical questions
_HIGH = [
    "Analyze treatment options for {age}-year-old diabetic patient {name} with elevated HbA1c",
    "Patient {name}, MRN: {mrn}, presents with hypertension; adjust lisinopril dosage",
    "Summarize lab results for patient {name}, SSN {ssn}, diagnosed with asthma",
    "Draft a referral for {name} (DOB: 1979-03-{dd}) regarding chemotherapy schedule",
    "Patient {name} reports depression symptoms; review sertraline treatment plan",
]
_NAMES = ["John Doe", "Alice Johnson", "Maria Garcia", "Wei Chen",
          "Priya Patel"]


def clinical_question(rng: random.Random):
    """A filled template and the identifying values it carries (the
    patient's name, and the MRN or SSN where the template has one)."""
    t = rng.choice(_HIGH)
    fill = dict(age=rng.randint(25, 80), name=rng.choice(_NAMES),
                mrn=rng.randint(10 ** 5, 10 ** 6),
                ssn=f"{rng.randint(100, 999)}-{rng.randint(10, 99)}-"
                    f"{rng.randint(1000, 9999)}",
                dd=rng.randint(10, 28))
    pii = [fill["name"]]
    if "{mrn}" in t:
        pii.append(str(fill["mrn"]))
    if "{ssn}" in t:
        pii.append(fill["ssn"])
    return t.format(**fill), pii


# ------------------------------------------------------------ requests


@dataclass
class Req:
    """One request of a run. ``due`` is seconds after the window opens
    (open loop) or None (closed loop: sent when a slot of the loop
    frees)."""
    idx: int
    prompt: str
    max_new: int
    tier: int
    tenant: str
    priority: str
    due: float | None
    shared_id: int          # shared head / document rank, -1 = none
    pii: tuple = ()         # identifying values in the prompt


def load(name: str) -> dict:
    with open(TRAFFIC_DIR / f"{name}.json") as f:
        return json.load(f)


def _shapes(t: dict, n: int) -> tuple:
    """The fixed sizes of ``n`` requests and the shared corpus, from the
    traffic file's own seed: (per-request tuples, corpus lengths, gaps)."""
    rng = random.Random(t["shape_seed"])
    sh = t.get("shared")
    corpus = []
    if sh:
        corpus = [bounded_pareto_int(rng, sh.get("alpha", 1.0), sh["min"],
                                     sh["max"])
                  for _ in range(sh["corpus"])]
        zipf = Zipf(sh["corpus"], sh["zipf_s"])
    tiers = [x for x, _ in t["tiers"]]
    tier_w = [w for _, w in t["tiers"]]
    P, O, Q = t.get("prompt"), t["output"], t.get("question")
    reqs = []
    for _ in range(n):
        tier = tiers[mixture_index(rng, tier_w)]
        out = bounded_pareto_int(rng, O["alpha"], O["min"], O["max"])
        sid = -1
        if sh and rng.random() < sh["reuse_p"]:
            sid = zipf.sample(rng)
        if Q:  # document + question
            body = bounded_pareto_int(rng, Q["alpha"], Q["min"], Q["max"])
        else:  # whole prompt length; a shared head is part of it
            body = bounded_pareto_int(rng, P["alpha"], P["min"], P["max"])
        reqs.append((tier, out, sid, body))
    gaps = [rng.expovariate(1.0) for _ in range(n + 1)]
    return reqs, corpus, gaps


def window_count(t: dict, seconds: float) -> int:
    """Requests due in a window of ``seconds`` (open loop), or the size
    of the pool a closed loop draws from."""
    a = t["arrival"]
    if a["kind"] == "poisson":
        return max(1, round(a["rate_per_s"] * seconds))
    # closed loop: enough for the loop to never run dry in the window
    return max(a["outstanding"], round(a["max_completions_per_s"] * seconds)
               + 2 * a["outstanding"])


def build(t: dict, seed: int, seconds: float, phase: str = "window"):
    """The requests of one run: the window's (``phase="window"``, their
    text from ``seed``) or the warm-up stream (``phase="warmup"``, the
    same for every seed). Returns (requests, corpus texts)."""
    if phase == "warmup":
        n = t["warmup"]["requests"]
        order_seed = t["warmup"]["seed"]
    else:
        n = window_count(t, seconds)
        order_seed = seed
    reqs, corpus_lens, gaps = _shapes(t, n)
    rng = random.Random(order_seed)
    corpus = [words(rng, L) for L in corpus_lens]
    tenants = Zipf(t["tenants"]["n"], t["tenants"]["zipf_s"])
    a = t["arrival"]
    due = None
    if a["kind"] == "poisson" and phase == "window":
        # N arrivals inside the window, at the file's rate exactly
        scale = seconds / sum(gaps)
        acc, due = 0.0, []
        for g in gaps[:n]:
            acc += g * scale
            due.append(acc)
    elif a["kind"] == "poisson":
        # warm-up: the same rate, from the warm-up's own gaps
        rate = a["rate_per_s"]
        acc, due = 0.0, []
        for g in gaps[:n]:
            acc += g / rate
            due.append(acc)
    out = []
    Q = t.get("question")
    for i, (tier, max_new, sid, body) in enumerate(reqs):
        shared_len = corpus_lens[sid] if sid >= 0 else 0
        pii = ()
        if Q:
            q, pii = clinical_question(rng)
            q = q + " " + words(rng, max(0, body - len(q) - 1))
            prompt = (corpus[sid] + " " if sid >= 0 else "") + q.rstrip()
        elif sid >= 0:
            prompt = corpus[sid] + words(rng, max(1, body - shared_len))
        else:
            prompt = words(rng, body)
        out.append(Req(idx=i, prompt=prompt, max_new=max_new, tier=tier,
                       tenant=f"t{tenants.sample(rng):02d}",
                       priority=t["priority"],
                       due=due[i] if due is not None else None,
                       shared_id=sid,
                       pii=tuple(pii)))
    return out, corpus


def encode(text: str) -> list:
    """Byte tokens as the served model sees them: a begin token (1), then
    each UTF-8 byte offset by the three special ids."""
    return [1] + [3 + b for b in text.encode("utf-8")]


def reach_request(t: dict, seed: int, max_new: int) -> Req:
    """A request whose context reaches the longest any request of the
    traffic can: the longest prompt plus the longest answer, with the
    answer cut to ``max_new``."""
    O, Q, sh = t["output"], t.get("question"), t.get("shared")
    if Q:
        longest = (sh["max"] + 1 if sh else 0) + Q["max"] + 24
    else:
        longest = t["prompt"]["max"]
    n = longest + O["max"] - max_new
    return Req(idx=-1, prompt=words(random.Random(seed), n), max_new=max_new,
               tier=t["tiers"][0][0], tenant="t00", priority=t["priority"],
               due=None, shared_id=-1)
