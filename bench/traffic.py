"""The one traffic generator: every mix is a data file of parameters
(``bench/traffic/<name>.json``) that this module reads.

Keys of a traffic file:

* ``arrival``: ``{"kind": "closed", "clients": C, "think_s": t}`` — each
  client sends its next request ``t`` seconds after its previous one
  finished — or ``{"kind": "open", "rate_per_s": r, "burst": {"size": k,
  "every_s": T}}`` — Poisson arrivals at rate ``r``, plus ``k`` requests
  at once every ``T`` seconds if ``burst`` is given.
* ``prompt``: ``{"lengths": [...]}`` — a fixed cycle of prompt lengths in
  the order listed (client ``c`` starts ``c·len/C`` entries in), or
  ``{"dist": "loguniform" | "uniform", "min": a, "max": b}``.
* ``shared_prefix`` (optional): ``{"count": n, "tokens": s}`` — every
  prompt starts with one of ``n`` prefixes of ``s`` tokens, drawn from
  the seed, and its length counts them.
* ``output_tokens``: a number, or ``{"min": a, "max": b}``.
* ``warmup`` (optional): lists of prompt lengths served together before
  the window, one list per admission wave; by default each distinct
  length of a ``lengths`` cycle alone.

The seed sets the token ids (and, for distributions, the draws); the
lengths of a cycle and their order belong to the mix.
"""

from __future__ import annotations

import zlib

import numpy as np


class Traffic:
    def __init__(self, spec: dict, seed: int, vocab: int):
        self.spec = spec
        self.seed = int(seed)
        self.vocab = int(vocab)
        arrival = spec["arrival"]
        self.kind = arrival["kind"]
        if self.kind not in ("closed", "open"):
            raise ValueError(f"unknown arrival kind {self.kind!r}")
        self.clients = int(arrival.get("clients", 1))
        self.think_s = float(arrival.get("think_s", 0.0))
        sp = spec.get("shared_prefix") or {}
        self._prefixes = [
            self._rng("prefix", i).integers(0, self.vocab, int(sp["tokens"]),
                                            dtype=np.int32)
            for i in range(int(sp.get("count", 0)))]

    def _rng(self, *parts) -> np.random.Generator:
        words = [self.seed & 0xFFFFFFFF, self.seed >> 32]
        for p in parts:
            words.append(p if isinstance(p, int) else zlib.crc32(p.encode()))
        return np.random.default_rng(words)

    def prompt_len(self, client: int, index: int) -> int:
        p = self.spec["prompt"]
        if "lengths" in p:
            lens = p["lengths"]
            offset = client * len(lens) // max(self.clients, 1)
            return int(lens[(index + offset) % len(lens)])
        rng = self._rng("len", client, index)
        lo, hi = float(p["min"]), float(p["max"])
        if p["dist"] == "loguniform":
            return int(round(np.exp(rng.uniform(np.log(lo), np.log(hi)))))
        if p["dist"] == "uniform":
            return int(rng.integers(int(lo), int(hi) + 1))
        raise ValueError(f"unknown prompt distribution {p['dist']!r}")

    def output_tokens(self, client: int, index: int) -> int:
        o = self.spec["output_tokens"]
        if isinstance(o, dict):
            return int(self._rng("out", client, index).integers(
                int(o["min"]), int(o["max"]) + 1))
        return int(o)

    def prompt(self, client: int, index: int, length: int | None = None) -> np.ndarray:
        n = self.prompt_len(client, index) if length is None else length
        rng = self._rng("tok", client, index)
        head = np.zeros(0, np.int32)
        if self._prefixes:
            head = self._prefixes[int(rng.integers(len(self._prefixes)))][:n]
        tail = rng.integers(0, self.vocab, n - len(head), dtype=np.int32)
        return np.concatenate([head, tail]).astype(np.int32)

    def warmup_waves(self) -> list[list[int]]:
        if "warmup" in self.spec:
            return [list(map(int, w)) for w in self.spec["warmup"]]
        lens = self.spec["prompt"].get("lengths")
        if lens is None:
            raise ValueError("a prompt distribution needs explicit warmup waves")
        return [[n] for n in dict.fromkeys(int(x) for x in lens)]

    def arrivals(self, seconds: float) -> list[float]:
        """Open loop: send times (s from the window's start)."""
        a = self.spec["arrival"]
        rng = self._rng("arrivals")
        times, t = [], 0.0
        rate = float(a["rate_per_s"])
        while rate > 0:
            t += rng.exponential(1.0 / rate)
            if t >= seconds:
                break
            times.append(t)
        burst = a.get("burst")
        if burst:
            every = float(burst["every_s"])
            times += [k * every for k in range(int(seconds // every) + 1)
                      for _ in range(int(burst["size"]))
                      if k * every < seconds]
        return sorted(times)
