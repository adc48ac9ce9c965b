"""Test vectors of the benchmark, and the plain evaluation that every
evaluated lane is compared with."""
from __future__ import annotations

import random

import numpy as np

from .netlist import eval_netlist


def seeded_lanes(net, n_words: int, seed: int) -> dict[int, np.ndarray]:
    """Random packed test vectors for every primary input, one uint32
    word per 32 vectors — the same draw as the program's
    ``flow.random_lanes`` (Python's ``random.Random(seed)``, one
    ``getrandbits(32)`` per word, inputs in ``net.pis`` order)."""
    rng = random.Random(seed)
    return {s: np.array([rng.getrandbits(32) for _ in range(n_words)],
                        dtype=np.uint32) for s in net.pis}


def block_lanes(net, n_words: int, entropy) -> dict[int, np.ndarray]:
    """Random test vectors for every primary input from a numpy
    generator seeded with ``entropy`` (a list of non-negative ints)."""
    rng = np.random.default_rng(entropy)
    words = rng.integers(0, 2**32, size=(len(net.pis), n_words),
                         dtype=np.uint32)
    return dict(zip(net.pis, words))


def words_int(words) -> int:
    """Packed uint32 lane words -> one int, word 0 in the low bits."""
    return int.from_bytes(np.asarray(words, dtype="<u4").tobytes(), "little")


def reference_pos(ref_net, pi_words: dict, half_words: bool = False
                  ) -> dict[int, list[int]]:
    """Every primary output's lane words from the plain evaluation of
    ``ref_net`` on ``pi_words`` (signal -> uint32 words).

    ``half_words=True`` is the control: the evaluation on 16-bit lane
    words, so the upper 16 vectors of every 32-bit word are never
    computed and read as 0."""
    n_words = len(next(iter(pi_words.values()))) if pi_words else 1
    width = 32 * n_words
    ref = eval_netlist(ref_net, {s: words_int(w) for s, w in
                                 pi_words.items()}, width)
    keep = np.uint32(0xFFFF if half_words else 0xFFFFFFFF)
    return {s: int_words(ref[s], n_words) & keep
            for bus in ref_net.pos.values() for s in bus}


def int_words(value: int, n_words: int) -> np.ndarray:
    """One int -> ``n_words`` packed uint32 lane words, the inverse of
    :func:`words_int`."""
    return np.frombuffer(value.to_bytes(4 * n_words, "little"),
                         dtype="<u4").astype(np.uint32)


def count_mismatches(got: dict, want: dict) -> int:
    """How many (primary output, lane word) pairs differ; an output
    missing from ``got`` counts every one of its words."""
    bad = 0
    for s, ws in want.items():
        g = got.get(s)
        if g is None:
            bad += len(ws)
            continue
        bad += int(np.count_nonzero(np.asarray(g, dtype=np.uint32)
                                    != np.asarray(ws, dtype=np.uint32)))
    return bad
