"""Uncertainty-bearing chat utterances, copied from the program's
``core/datagen.py`` so that the benchmark's traffic stays fixed when the
program changes.

The six uncertainty types of RT-LM (structural, syntactic, semantic,
vague, open-ended, multi-part) plus plain utterances are slot-filled
from template banks.  Each utterance carries a true uncertainty ``u``
derived from its template slots; a persona maps it to an output length

    len = clip(round(base + gain * u + N(0, noise)), 1, max_output)

which keeps the correlation between uncertainty and output length that
the uncertainty-aware scheduler exploits.
"""

from __future__ import annotations

import random

# ---- template banks (copied verbatim) ----

_NAMES = ["john", "mary", "the officer", "my friend", "the teacher",
          "a student", "the doctor", "anna", "the researcher", "tom"]
_NOUNS = ["boy", "dog", "bird", "painting", "robot", "car", "statue",
          "kite", "drone", "violin"]
_PLACES = ["park", "garden", "museum", "street", "library", "station",
           "market", "forest", "harbor", "stadium"]
_INSTR = ["telescope", "camera", "umbrella", "flashlight", "map",
          "binoculars", "ladder", "net", "whistle", "radio"]
_AMBIG_SUBJ = ["rice", "time", "fruit", "sand", "dust", "seed", "water"]
_AMBIG_VERBS = ["flies", "runs", "walks", "races", "files", "rounds"]
_POLY = ["bat", "trunk", "monitor", "bank", "spring", "pitch", "crane",
         "seal", "bolt", "club", "match", "scale", "ring", "wave", "bar",
         "key", "bug", "mole", "port"]
_TOPICS = ["art", "music", "science", "philosophy", "technology",
           "medicine", "education", "architecture", "literature",
           "economics"]
_ISSUES = ["poverty", "climate change", "inequality", "urbanization",
           "automation", "migration", "pollution", "aging populations",
           "misinformation", "unemployment"]
_REGIONS = ["developing countries", "coastal cities", "rural areas",
            "modern societies", "large cities", "small towns"]
_PAIR_A = ["cats", "trains", "novels", "lakes", "pianos", "bees"]
_PAIR_B = ["dogs", "planes", "films", "rivers", "guitars", "ants"]
_ASPECTS = ["behavior", "diet", "cost", "history", "maintenance",
            "social interaction", "structure", "speed", "sound", "habitat"]
_PLAIN = [
    "i had pasta for dinner yesterday.",
    "the train leaves at seven tomorrow.",
    "my sister lives near the station.",
    "it rained all day on monday.",
    "please pass the salt.",
    "the meeting starts at noon.",
    "i bought two tickets for the show.",
    "she finished the report on friday.",
    "the shop closes at nine.",
    "we walked home after lunch.",
]


def utterance(utype: str, rng: random.Random):
    """One ``(text, true_uncertainty)`` of the given type."""
    if utype == "structural":
        n_pp = rng.choice([2, 2, 3])
        pps = rng.sample(
            [f"in the {rng.choice(_PLACES)}", f"with a {rng.choice(_INSTR)}",
             f"near the {rng.choice(_PLACES)}", f"by the {rng.choice(_PLACES)}"],
            n_pp)
        text = (f"{rng.choice(_NAMES)} saw a {rng.choice(_NOUNS)} "
                + " ".join(pps) + ".")
        u = 2.0 + 1.6 * (n_pp - 1) + rng.uniform(-0.4, 0.4)
    elif utype == "syntactic":
        n = rng.choice([1, 2, 2, 3])
        subj = rng.choice(_AMBIG_SUBJ)
        verb = rng.choice(_AMBIG_VERBS)
        tail = rng.choice(["like sand", "like an arrow", "like a bird",
                           "like water"])
        extra = " and ".join(rng.sample(_AMBIG_VERBS, max(0, n - 1)))
        text = f"{subj} {verb} {tail}" + (f" and {extra}." if extra else ".")
        u = 1.6 + 1.2 * n + rng.uniform(-0.4, 0.4)
    elif utype == "semantic":
        n = rng.choice([1, 2, 2, 3])
        words = rng.sample(_POLY, n)
        frame = rng.choice([
            "what's the best way to deal with {w}?",
            "i saw a {w} near the {p}.",
            "can you explain what a {w} is?",
            "the {w} by the {p} surprised everyone.",
        ])
        text = frame.format(w=words[0], p=rng.choice(_PLACES))
        for w in words[1:]:
            text += f" also, what about the {w}?"
        u = 3.0 + 1.8 * n + rng.uniform(-0.5, 0.5)
    elif utype == "vague":
        depth = rng.choice([1, 2, 2, 3])
        text = rng.choice([
            "tell me about the {a} of {t}.",
            "can you talk about the {a} of {t}?",
            "i want to know about the {a} of {t} in general.",
        ]).format(a=rng.choice(["history", "nature", "philosophy",
                                "meaning", "future"]),
                  t=rng.choice(_TOPICS))
        if depth >= 2:
            text += " cover many broad aspects."
        if depth >= 3:
            text += " include the whole general context."
        u = 5.5 + 1.8 * depth + rng.uniform(-0.6, 0.6)
    elif utype == "open_ended":
        depth = rng.choice([1, 2, 2, 3])
        text = rng.choice([
            "what are the causes and consequences of {i} in {r}?",
            "why do {i} keep getting worse in {r}?",
            "how could {r} address {i} over time?",
            "what do you think about {i}?",
        ]).format(i=rng.choice(_ISSUES), r=rng.choice(_REGIONS))
        if depth >= 2:
            text += " please give reasons and implications."
        if depth >= 3:
            text += " what is the long term significance?"
        u = 6.0 + 2.0 * depth + rng.uniform(-0.7, 0.7)
    elif utype == "multi_part":
        k = rng.choice([2, 3, 3, 4])
        aspects = rng.sample(_ASPECTS, k)
        text = (f"how do {rng.choice(_PAIR_A)} and {rng.choice(_PAIR_B)} "
                f"differ in {', '.join(aspects[:-1])}, and {aspects[-1]}?")
        if rng.random() < 0.4:
            text += " and which is better overall?"
        u = 5.0 + 1.7 * k + rng.uniform(-0.6, 0.6)
    else:  # plain
        text = rng.choice(_PLAIN)
        u = 0.4 + 0.08 * len(text.split()) + rng.uniform(-0.2, 0.2)
    return text, max(0.1, u)


def output_length(u: float, persona: dict, rng: random.Random) -> int:
    """The persona's output length for true uncertainty ``u``."""
    ln = persona["base_output"] + persona["uncertainty_gain"] * u \
        + rng.gauss(0.0, persona["noise_std"])
    return int(min(max(round(ln), 1), persona["max_output"]))
