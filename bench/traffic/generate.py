"""The one generator of every traffic mix: it reads a mix's data file
(``bench/traffic/<mix>.json``) and makes the inputs of one run from its
``--seed``.

Shapes are drawn once from the mix's own ``shape_seed``: request lengths,
system-prompt choices and inter-arrival gaps form one fixed schedule, so
every run seed does the same work at the same times, and the tails of a
run near the engine's capacity do not swing with the order of a few long
requests. The run seed draws the token ids. Lengths follow clipped lognormals
(heavy tails) or uniforms; system prompts are chosen with Zipf skew over
their ranks; arrivals are a Poisson process at the mix's rate (open loop),
or all at t = 0.

Token ids are drawn uniformly from ``[first_id, vocab_size)`` of the
configuration (ids below ``first_id`` are left to special tokens).
"""
from __future__ import annotations

import zlib
from typing import Dict, List

import numpy as np


def rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([int(seed), zlib.crc32(stream.encode())]))


def lengths(g: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    if "lognormal_median" in spec:
        x = np.exp(np.log(spec["lognormal_median"])
                   + spec["sigma"] * g.standard_normal(n))
    elif "uniform_min" in spec:
        x = g.uniform(spec["uniform_min"], spec["uniform_max"] + 1, n)
    else:
        x = np.full(n, float(spec["fixed"]))
    return np.clip(np.floor(x), spec.get("min", 1),
                   spec.get("max", np.inf)).astype(np.int64)


def zipf_choice(g: np.random.Generator, n_items: int, s: float,
                n: int) -> np.ndarray:
    p = 1.0 / np.arange(1, n_items + 1) ** s
    return g.choice(n_items, size=n, p=p / p.sum())


def token_rows(mix: dict, n_rows: int, seq_len: int, seed: int,
               vocab: int) -> np.ndarray:
    """Training rows: ``[n_rows, seq_len + 1]`` uint32 token ids (inputs
    and next-token labels), every row a distinct draw."""
    g = rng(seed, "train_tokens")
    return g.integers(mix.get("first_id", 3), vocab,
                      size=(n_rows, seq_len + 1), dtype=np.uint32)


def request_count(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["rate_per_s"] * seconds)))


def requests(mix: dict, seconds: float, seed: int,
             vocab: int) -> List[Dict]:
    """Serving requests of one run: dicts with ``rid``, ``prompt`` (int32
    ids), ``max_new`` (output tokens), ``arrival_s`` and ``system``
    (the system prompt's index, -1 for none)."""
    n = request_count(mix, seconds)
    shape = rng(mix["shape_seed"], f"shapes/{n}")
    sysp = mix.get("system_prompts")
    if sysp:
        which = zipf_choice(shape, sysp["count"], sysp["zipf_s"], n)
    else:
        which = np.full(n, -1)
    tails = lengths(shape, mix["prompt_len"], n)
    outs = lengths(shape, mix["output_len"], n)
    if mix["arrival"] == "poisson":
        gaps = shape.exponential(1.0 / mix["rate_per_s"], n)
    elif mix["arrival"] == "all_at_once":
        gaps = np.zeros(n)
    else:
        raise ValueError(f"unknown arrival process {mix['arrival']!r}")
    arrivals = np.cumsum(gaps) - gaps[0]
    g = rng(seed, "requests")
    first = mix.get("first_id", 3)
    prompts = ([g.integers(first, vocab, size=sysp["len"], dtype=np.int32)
                for _ in range(sysp["count"])] if sysp else [])
    out = []
    for i in range(n):
        tail = g.integers(first, vocab, size=int(tails[i]), dtype=np.int32)
        s = int(which[i])
        prompt = np.concatenate([prompts[s], tail]) if s >= 0 else tail
        out.append({"rid": i, "prompt": prompt, "max_new": int(outs[i]),
                    "arrival_s": float(arrivals[i]), "system": s})
    return out
